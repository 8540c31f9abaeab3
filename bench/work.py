"""Operations and bytes each kernel's algorithm needs for a call, from
the cell's parameters and never from the implementation (which pads rows
to tiles and queries to blocks).

Bytes per stored element come from the row codec: fp32 4, bf16 2, int8 1.
"""
from __future__ import annotations

ELEMENT_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def beam_search(queries: int, ef: int, m: int, dim: int,
                dtype: str = "fp32") -> tuple[float, float]:
    """Layer-0 beam search: per query, ef expansions of 2M neighbours,
    each neighbour's row read once and scored with a D-long dot product
    (2·D operations). -> (flops, bytes)."""
    rows = queries * ef * 2 * m
    row_bytes = dim * ELEMENT_BYTES[dtype] + (4 if dtype == "int8" else 0)
    return float(rows * 2 * dim), float(rows * row_bytes)


def distance_topk(queries: int, rows: int, dim: int,
                  dtype: str = "fp32") -> tuple[float, float]:
    """Exact scan: every query against every row (2·B·N·D operations);
    the rows read once with their int8 scales, the queries once as fp32.
    -> (flops, bytes)."""
    scales = 4 * rows if dtype == "int8" else 0
    nbytes = rows * dim * ELEMENT_BYTES[dtype] + scales + queries * dim * 4
    return float(2 * queries * rows * dim), float(nbytes)
