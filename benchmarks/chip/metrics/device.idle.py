"""Share of the traced window in which no op ran on the device, averaged
over the chips (device trace: the union of each chip's ``XLA Ops``
intervals)."""


def read(ctx):
    busy = ctx.busy_ns()
    if not busy:
        return None
    win = ctx.window_ns
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / win)
