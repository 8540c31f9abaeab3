"""device.idle_share: share of the traced window in which no operation
ran on the device (1 - union of op intervals / window), averaged over
the chips used."""


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    return 100.0 * (1.0 - ctx.trace.mean_busy_ns(lo, hi) / (hi - lo))
