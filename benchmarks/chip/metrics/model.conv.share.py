"""Share of device busy time, in %, of the ops inside a ``conv`` scope
(``nn.conv.conv2d_apply``, forward and backward, client and server),
averaged over the chips; ``None`` without the round's scope map
(``scopes.of_program``)."""
from chip import scopes as SC


def read(ctx):
    return SC.kind_share(ctx, "conv")
