"""Aggregation kernel: the fused AirComp kernel's device time over the
device's busy time, in %, averaged over the chips."""


def read(ctx):
    from perfbench.metrics.aircomp_roofline_pct import kernel_ns

    if ctx.red is None:
        return None
    shares = []
    for d in ctx.red.devices:
        ns, count = kernel_ns(d)
        if count == 0:
            return None
        shares.append(100.0 * ns / d.busy_ns)
    return sum(shares) / len(shares)
