"""recall_at_10: mean share of the exact top-10 (float64 cosine over the
fp32 corpus rows) among the keys the engine returned, over the first
8,192 requests of the run's stream, which every run answers (fewer
where a run answers fewer in order)."""
import numpy as np


def read(ctx):
    truth = ctx.recall["truth"]
    if truth is None:
        return None
    hits = [0 if found is None else len(set(found.tolist())
                                        & set(t.tolist()))
            for found, t in zip(ctx.recall["found"], truth)]
    return float(np.sum(hits) / truth.size)
