"""Distributed retrieval over a STATIC array: DB rows sharded over the
whole mesh, per-shard top-k + hierarchical merge (DESIGN.md §4/§8).

This is the pod-scale version of the paper's on-device search: "on-device"
becomes "on-pod" — the whole corpus lives in pod HBM, no external vector
service is consulted, and a query costs one log-depth top-k tree reduction.

The MUTABLE generalization of this helper lives in ``core/sharded.py``:
``ShardedRows`` adds keyed CRUD, deterministic key->shard routing, and
per-shard free-slot bookkeeping on top of the same fan-out/merge dataflow,
and is what the ``VectorIndex`` backends are built on. This module stays
as the thin static-array entry point the dry-run/HLO tooling uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.distributed.collectives import hierarchical_topk
from repro.kernels import ops


def sharded_flat_topk(mesh: Mesh, db: jax.Array, queries: jax.Array, k: int,
                      *, metric: str = "cosine",
                      wire_bf16: bool = False) -> tuple[jax.Array, jax.Array]:
    """db [N, D] (rows sharded over every mesh axis), queries [B, D]
    (replicated) -> (dists [B, k], global ids [B, k]) replicated.

    N need not be a multiple of the shard count: the DB is padded up to
    one with sentinel rows whose ids are masked to (-1, INF) BEFORE the
    merge — previously ``n // n_shards`` silently dropped the trailing
    ``N mod S`` rows from the search. Because the sentinel rows' vector
    payload is zeros (their distances can rank arbitrarily well, e.g.
    cosine distance 1.0), each shard over-fetches ``k + pad`` local
    candidates, masks, and re-selects k — padding can therefore never
    displace a real row from the local top-k.
    """
    axes = tuple(mesh.axis_names)
    n = db.shape[0]
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    rows_per = -(-n // n_shards)               # ceil: nothing dropped
    pad = rows_per * n_shards - n
    if pad:
        db = jnp.concatenate(
            [db, jnp.zeros((pad, db.shape[1]), db.dtype)], axis=0)

    def local(db_l, q_l):
        kk = min(rows_per, k + pad)
        d, i = ops.flat_topk(db_l, q_l.astype(db_l.dtype), kk, metric=metric)
        if wire_bf16:
            # genuinely bf16 from the source: leaves XLA no convert to
            # commute above the merge all-gathers (wire bytes halve)
            d = d.astype(jnp.bfloat16)
        shard_id = jnp.zeros((), jnp.int32)
        for a in axes:                       # row-major flattened shard index
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)
        i = i + shard_id * rows_per
        # sentinel mask: padded rows (global id >= n) must not reach the
        # merge — their distance becomes +inf and their id -1
        from repro.core.sharded import trim_merge_width
        sentinel = i >= n
        d = jnp.where(sentinel, jnp.asarray(jnp.inf, d.dtype), d)
        i = jnp.where(sentinel, -1, i)
        d, i = trim_merge_width(d, i, k, jnp.asarray(jnp.inf, d.dtype))
        # innermost axis first: smallest hop first in the merge tree;
        # static axis sizes engage the ppermute tree reduction per axis
        merge_axes = tuple(reversed(axes))
        return hierarchical_topk(d, i, k, merge_axes, wire_bf16,
                                 axis_sizes=tuple(int(mesh.shape[a])
                                                  for a in merge_axes))

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axes, None), P(None, None)),
                   out_specs=(P(None, None), P(None, None)),
                   check_vma=False)   # post-merge values ARE replicated
    return fn(db, queries)


def make_retrieval_step(mesh: Mesh, k: int, metric: str = "cosine"):
    """jit-able retrieval step for the dry-run: (db, q) -> (dists, ids)."""

    @functools.partial(jax.jit,
                       in_shardings=(NamedSharding(mesh, P(tuple(mesh.axis_names), None)),
                                     NamedSharding(mesh, P(None, None))),
                       out_shardings=NamedSharding(mesh, P(None, None)))
    def retrieval_step(db, q):
        return sharded_flat_topk(mesh, db, q, k, metric=metric)

    return retrieval_step
