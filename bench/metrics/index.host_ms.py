"""index.host_ms: median over the traced query_batch spans of the span's
length less the device's busy time inside it: the index layer's host
work (query prep, rerank, id-to-key mapping) and dispatch gaps."""
import numpy as np


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    devices = list(ctx.trace.ops)
    host = [((e.end - e.start) - sum(ctx.trace.busy_ns(d, e.start, e.end)
                                     for d in devices) / len(devices)) / 1e6
            for e in ctx.trace.spans("index.query_batch")
            if lo <= e.start <= hi]
    return float(np.median(host)) if host else None
