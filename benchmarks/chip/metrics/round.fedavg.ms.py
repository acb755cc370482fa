"""Device ms per round of the ops in the round's ``sfpl.fedavg`` scope:
the FedAvg of the client models and the BatchNorm state's aggregation,
averaged over the chips; ``None`` without the round's scope map
(``scopes.of_ctx``)."""
from chip import scopes as SC


def read(ctx):
    return SC.phase_ms(ctx, ("fedavg", None))
