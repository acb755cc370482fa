"""Device ms per round of the ops in the round's ``sfpl.client_fwd`` scope:
the clients' forward (``vmap`` over the client axis), averaged over the
chips; ``None`` without the round's scope map (``scopes.of_program``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("client_fwd", None))
