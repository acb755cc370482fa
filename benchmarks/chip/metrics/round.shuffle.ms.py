"""Device ms per round of the ops in the round's ``sfpl.shuffle`` scope,
forward and transposed: the permutation, its route plans, the label and
activation permutes and the route back, averaged over the chips; ``None``
without the round's scope map (``scopes.of_ctx``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("shuffle", None))
