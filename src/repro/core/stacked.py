"""One-dispatch segment fan-out for graph-backed shards (DESIGN.md §8).

A sharded HNSW is a segment set: each shard owns an independent graph
over its hash-routed keys. The original sharded ``query_batch`` looped
``child.query_batch(...)`` in Python — S device dispatches plus a host
merge per batch, which is exactly the S=8 latency cliff BENCH smoke
measured (per-shard scan time shrinks with S, dispatch + host merge
grows with it).

This module compiles the whole fan-out into ONE XLA program at any
shard count: the per-shard ``DeviceGraph`` pytrees are stacked along a
leading [S, ...] axis (capacity-padded to the largest shard; padded
rows are unreachable — no inbound edges — and masked via the existing
tombstone machinery), the lock-step beam search runs per shard under
``shard_map`` on the shard mesh, and the per-shard candidates merge
in-program through the ppermute tree reduction
(``hierarchical_topk``). Global result ids are ``gid = s * cap + node``
so the caller can invert them to (shard, node) without a table.

The stacked arrays are built from the children's RESIDENT device
graphs (device-side pad + stack, no host repack) and are meant to be
cached by the index keyed on ``mutation_epoch`` — steady-state sharded
search then touches zero host bytes and issues exactly one dispatch.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dispatch
from repro.core import hnsw as jhnsw
from repro.core.sharded import SHARD_AXIS, resolve_wire_bf16
from repro.distributed.collectives import hierarchical_topk
from repro.kernels.layout import LANES, PackedRows

INF = np.float32(3e38)

# incremented once per compiled stacked-search invocation: tests assert
# a sharded ``query_batch`` is exactly ONE device dispatch at any S.
# Kept as the historical module global; the named counters in
# core/dispatch.py ("stacked.search_stacked", "stacked.beam_launches")
# are bumped in lockstep.
DISPATCH_COUNT = 0


@dataclasses.dataclass(frozen=True)
class StackedGraphs:
    """Per-shard DeviceGraphs stacked along a leading [S, ...] axis,
    capacity-padded to the largest shard and resident on the shard mesh.
    Empty shards hold an all-tombstoned placeholder so the mesh size is
    always exactly the index's shard count."""
    mesh: Mesh
    vectors: jax.Array      # [S, cap, D] storage dtype (DESIGN.md §9)
    neighbors0: jax.Array   # [S, cap/R, 128] int32: packed [cap, 2M], -1 pad
    upper: jax.Array        # [S, L·cap/R, 128] int32: packed [L·cap, M]
    levels: jax.Array       # [S, cap] int32
    entry: jax.Array        # [S] int32
    deleted: jax.Array      # [S, cap] bool tombstones
    scales: jax.Array | None  # [S, cap] f32 decode scales (int8 codec)
    max_level: int          # max over shards: static descent unroll depth
    metric: str
    cap: int                # padded per-shard capacity: gid = s*cap + node
    m2: int                 # layer-0 neighbor width (2M)
    m: int                  # upper-layer neighbor width (M)


def stack_device_graphs(graphs: list[jhnsw.DeviceGraph | None],
                        mesh: Mesh) -> StackedGraphs:
    """Stack per-shard resident graphs (None = empty shard) into one
    [S, ...] pytree sharded over ``mesh``. All inputs are device arrays,
    so padding + stacking is device work — the host never rebuilds row
    blocks (contrast the exact phase's ``build_exact_blocks``)."""
    live = [g for g in graphs if g is not None]
    if not live:
        raise ValueError("index is empty")
    proto = live[0]
    cap = max(g.n for g in live)         # each a device_capacity: aligned
    layers = proto.n_layers
    m, per_u = proto.upper.width, proto.upper.per
    m2, per = proto.neighbors0.width, proto.neighbors0.per
    dim = proto.vectors.shape[1]
    has_scales = proto.scales is not None
    vecs, n0s, ups, lvls, ents, dels, scls = [], [], [], [], [], [], []
    for g in graphs:
        if g is None:
            # unreachable placeholder: no edges, entry 0, everything
            # tombstoned — the beam returns (INF, -1) for this shard
            vecs.append(jnp.zeros((cap, dim), proto.vectors.dtype))
            n0s.append(jnp.full((cap // per, LANES), -1, jnp.int32))
            ups.append(jnp.full((layers * cap // per_u, LANES), -1,
                                jnp.int32))
            lvls.append(jnp.zeros((cap,), jnp.int32))
            ents.append(jnp.zeros((), jnp.int32))
            dels.append(jnp.ones((cap,), bool))
            if has_scales:
                scls.append(jnp.zeros((cap,), jnp.float32))
            continue
        pad = cap - g.n
        vecs.append(jnp.pad(g.vectors, ((0, pad), (0, 0))))
        n0s.append(jnp.pad(g.neighbors0.table, ((0, pad // per), (0, 0)),
                           constant_values=-1))
        up = g.upper.table.reshape(layers, g.n // per_u, LANES)
        ups.append(jnp.pad(up, ((0, 0), (0, pad // per_u), (0, 0)),
                           constant_values=-1).reshape(-1, LANES))
        lvls.append(jnp.pad(g.levels, (0, pad)))
        ents.append(g.entry)
        dels.append(jnp.pad(g.deleted, (0, pad), constant_values=True))
        if has_scales:
            scls.append(jnp.pad(g.scales, (0, pad)))

    def put(x, *axes):
        return jax.device_put(x, NamedSharding(mesh, P(SHARD_AXIS, *axes)))

    return StackedGraphs(
        mesh=mesh,
        vectors=put(jnp.stack(vecs), None, None),
        neighbors0=put(jnp.stack(n0s), None, None),
        upper=put(jnp.stack(ups), None, None),
        levels=put(jnp.stack(lvls), None),
        entry=put(jnp.stack(ents)),
        deleted=put(jnp.stack(dels), None),
        scales=put(jnp.stack(scls), None) if has_scales else None,
        max_level=max(g.max_level for g in live),
        metric=proto.metric,
        cap=cap,
        m2=m2,
        m=m)


@functools.lru_cache(maxsize=32)
def _stacked_search_fn(mesh: Mesh, k: int, ef: int, metric: str,
                       max_level: int, has_scales: bool, wire_bf16: bool,
                       beam_impl: str, m2: int, m: int):
    """Compiled stacked fan-out: every shard runs the full lock-step
    search (``hnsw.search_core`` — greedy descent + ef-beam + tombstone
    filter) over its own slice, then the per-shard top-k merges through
    the ppermute tree. ``max_level`` is the max over shards: shards with
    shallower graphs see all-(-1) neighbor rows on the extra layers, so
    their descent terminates after one probe per layer.

    Cache keys are (mesh, k, ef, metric, max_level, has_scales,
    wire_bf16, beam_impl, m2, m) — all O(1)-valued per index configuration (max_level is
    bounded by the builder's layer cap), so the cache cannot churn."""
    n_shards = mesh.shape[SHARD_AXIS]

    def local(vectors, neighbors0, upper, levels, entry, deleted, q,
              scl=None):
        g = jhnsw.DeviceGraph(
            vectors=vectors[0], neighbors0=PackedRows(neighbors0[0], m2),
            upper=PackedRows(upper[0], m),
            levels=levels[0], entry=entry[0], deleted=deleted[0],
            max_level=max_level, metric=metric,
            scales=None if scl is None else scl[0])
        ids, d = jhnsw.search_core(g, q, k, ef, beam_impl=beam_impl)
        cap = vectors.shape[1]
        my = jax.lax.axis_index(SHARD_AXIS)
        gid = jnp.where(ids >= 0, my * cap + ids, -1)
        d = jnp.where(ids >= 0, d, jnp.float32(INF))
        return hierarchical_topk(d, gid, k, (SHARD_AXIS,),
                                 wire_bf16=wire_bf16, tie_break_ids=True,
                                 axis_sizes=(n_shards,))

    graph_specs = (P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None),
                   P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                   P(SHARD_AXIS), P(SHARD_AXIS, None))
    out_specs = (P(None, None), P(None, None))
    if has_scales:
        fn = shard_map(
            lambda vectors, neighbors0, upper, levels, entry, deleted,
            scl, q: local(vectors, neighbors0, upper, levels, entry,
                          deleted, q, scl),
            mesh=mesh,
            in_specs=graph_specs + (P(SHARD_AXIS, None), P(None, None)),
            out_specs=out_specs,
            check_vma=False)     # post-merge values ARE replicated
        return jax.jit(fn)
    fn = shard_map(local, mesh=mesh,
                   in_specs=graph_specs + (P(None, None),),
                   out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def search_stacked(st: StackedGraphs, queries, k: int, ef: int,
                   wire_bf16: bool | None = None,
                   beam_impl: str = "fused"
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Batched k-NN over a stacked segment set: queries [B, D] ->
    (dists [B, k], gids [B, k]), missing slots (INF, -1). One compiled
    dispatch regardless of shard count; the only per-query host->device
    movement is the query batch itself. ``beam_impl`` selects each
    shard's layer-0 beam (fused one-launch kernel vs jnp reference) —
    the same kernel rides under shard_map, so the fan-out stays a
    single dispatch either way."""
    global DISPATCH_COUNT
    q = jnp.asarray(queries, jnp.float32)
    if st.metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True),
                            1e-12)
    fn = _stacked_search_fn(st.mesh, k, max(ef, k), st.metric,
                            st.max_level, st.scales is not None,
                            resolve_wire_bf16(wire_bf16), beam_impl, st.m2,
                            st.m)
    DISPATCH_COUNT += 1
    dispatch.bump("stacked.search_stacked")
    dispatch.bump("stacked.beam_launches",
                  dispatch.beam_launches(beam_impl, max(ef, k)))
    if st.scales is not None:
        d, gid = fn(st.vectors, st.neighbors0, st.upper, st.levels,
                    st.entry, st.deleted, st.scales, q)
    else:
        d, gid = fn(st.vectors, st.neighbors0, st.upper, st.levels,
                    st.entry, st.deleted, q)
    return np.asarray(d), np.asarray(gid)
