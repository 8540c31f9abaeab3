"""Plain reference of the rows ``minilm-1m-flat-int8`` stores: each corpus
row cosine-normalised in float32, then scalar-quantized to int8 with one
float32 scale per row, max|x| / 127, codes rounded to nearest even and
clipped to [-127, 127]; a stored row is its codes times its scale. The
search is exact over those rows, so the returned keys are compared with
the exact top-k."""
import numpy as np

from bench.check import normalize32

EXACT = True


def stored_rows(corpus):
    x = normalize32(corpus)
    amax = np.max(np.abs(x), axis=-1)
    scale = np.where(amax > 0, amax / np.float32(127.0),
                     np.float32(1.0)).astype(np.float32)
    codes = np.clip(np.rint(x / scale[:, None]), -127, 127)
    return (codes * scale[:, None]).astype(np.float32)
