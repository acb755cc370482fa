"""Model FLOP/s utilization of the traced window: the model FLOPs of each
sample (the family's ``train_flops_per_sample``, from shapes: six times
the forward multiply-accumulates of client and server for a ResNet, the
client update's recomputed forward not counted) times the samples per
second of the traced rounds (host clock), over the chips' bf16 peak."""


def read(ctx):
    flops = ctx.family.train_flops_per_sample(ctx.config["model"])
    rate = ctx.samples_traced / ctx.window_s
    return 100.0 * flops * rate / (ctx.chips * ctx.peaks["bf16_flops"])
