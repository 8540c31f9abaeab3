"""Plain reference of the rows ``minilm-1m-hnsw`` stores: each corpus row
cosine-normalised in float32. HNSW is approximate, so which keys it
returns is not compared; the distance it gives each key is."""
from bench.check import normalize32

EXACT = False


def stored_rows(corpus):
    return normalize32(corpus)
