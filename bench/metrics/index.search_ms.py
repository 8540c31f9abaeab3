"""index.search_ms: median duration of the engine's calls to the index's
query_batch (a benchmark span around each; the call returns host arrays,
so the device work has finished)."""
import numpy as np


def read(ctx):
    if not ctx.calls:
        return None
    return float(np.median([(e - s) * 1e3 for s, e, _, _ in ctx.calls]))
