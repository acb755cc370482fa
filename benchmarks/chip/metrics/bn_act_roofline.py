"""Share of its roofline that the fused BN+activation kernel
(``sfpl_bn_act``) reaches: the least time of the round's BN epilogues,
each applied once per forward (unpadded bytes and ops from shapes; the
larger of bytes over HBM bandwidth and ops over peak, which is the
bytes), over the summed device time of the kernel's events, all chips."""


def read(ctx):
    tot = cnt = 0
    for t, c in ctx.op_time_ns(lambda n: n == "sfpl_bn_act").values():
        tot, cnt = tot + t, cnt + c
    if not cnt:
        return None
    ops, nbytes = ctx.flops.bn_act_cost(ctx.config["model"],
                                        ctx.config["fleet"],
                                        ctx.traffic["compute_dtype"])
    least, _ = ctx.flops.least_time_s(ops, nbytes, ctx.peaks)
    return 100.0 * least * ctx.rounds_traced / (tot / 1e9)
