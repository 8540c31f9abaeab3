"""engine.queue_ms: median time from a request's due time to the start of
the engine tick that dispatched it (benchmark host spans)."""
import numpy as np


def read(ctx):
    if ctx.queue_ms.size == 0:
        return None
    return float(np.median(ctx.queue_ms))
