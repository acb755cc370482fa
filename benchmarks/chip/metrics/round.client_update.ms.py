"""Device ms per round of the ops in the round's ``sfpl.client_update``
scope: each client's recomputed forward, its backward from the routed
gradients and its optimizer step, averaged over the chips; ``None``
without the round's scope map (``scopes.of_ctx``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("client_update", None))
