"""client.p50_ms: the median of the latencies p95_ms is taken from."""
import numpy as np


def read(ctx):
    if ctx.latency_ms.size == 0:
        return None
    return float(np.median(ctx.latency_ms))
