"""Share of device busy time, in %, of the ops inside a ``bn`` scope
(``nn.norm.batchnorm_apply`` and ``batchnorm_act_apply``, the fused
``sfpl_bn_act`` kernel among them, forward and backward), averaged over
the chips; ``None`` without the round's scope map (``scopes.of_program``)."""
from chip import scopes as SC


def read(ctx):
    return SC.kind_share(ctx, "bn")
