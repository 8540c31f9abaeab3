"""p95_ms: the 95th percentile of the latency of every answered request of
the window: from its due time (open loop) or its send time (closed loop)
to the end of the engine tick that handed its keys and distances back."""
import numpy as np


def read(ctx):
    if ctx.latency_ms.size == 0:
        return None
    return float(np.percentile(ctx.latency_ms, 95))
