"""Device ms per round of the forward ops in the round's ``sfpl.server``
scope: the server model and the loss on the shuffled pool, averaged over
the chips; ``None`` without the round's scope map (``scopes.of_program``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("server", "fwd"))
