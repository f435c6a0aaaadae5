"""Device: share of the traced window in which no op ran, in %, averaged
over the cell's chips (1 - union of op intervals / window)."""


def read(ctx):
    if ctx.red is None:
        return None
    return 100.0 * ctx.red.mean(lambda d: 1.0 - d.busy_ns / (ctx.red.window_s * 1e9))
