"""Batch-synchronous HNSW search in JAX (fixed shapes, lock-step).

The browser algorithm is pointer-chasing best-first search; on TPU every
query in the batch advances together (DESIGN.md §2):

  * upper layers: greedy descent, one hop per ``while_loop`` iteration, all
    queries stepping simultaneously until none improves;
  * layer 0: ef-beam best-first search. The beam is a sorted array of
    (dist, id, expanded); each iteration expands the best unexpanded entry of
    every query, gathers its 2M neighbors (the ``gather_distance`` hot spot —
    Pallas kernel on TPU, fused gather+dot here), merges candidates with a
    two-key sort and adjacent-duplicate masking.

Work per query  = ef expansions x 2M neighbor distances — identical to the
sequential algorithm's expansion budget, so recall matches the reference
builder (validated in tests/test_hnsw.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.hnsw_build import HNSWGraph
from repro.distributed.sharding import shard
from repro.kernels.layout import PackedRows, device_capacity, pack_rows

INF = jnp.float32(3.0e38)

# frontier nodes expanded per hop on the fused beam path (DESIGN.md §12):
# each DMA round amortizes over T nodes, so the one-launch kernel runs
# ceil(ef / T) hops against the same ef-expansion budget as the reference
DEFAULT_EXPAND_T = 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """HNSW graph as dense device tensors.

    ``deleted`` is the tombstone mask (DESIGN.md §3): tombstoned rows stay
    traversable during beam search (hnswlib-style, so graph connectivity
    survives deletions) but are excluded from returned results.

    ``vectors`` holds the rows in their STORAGE dtype (DESIGN.md §9):
    f32 historically, bf16/int8 under a lossy codec — with ``scales``
    carrying the int8 per-row decode scales. Every distance decodes in
    fp32 (fused into the gather kernel), so HBM holds the small encoding
    while the math stays asymmetric fp32.

    The graph owns the layout its kernels read (kernels/layout.py): N is
    the host capacity rounded up by ``device_capacity`` (a multiple of
    1024 rows; the extra rows are zero, edgeless and tombstoned), and the
    adjacency tables are stored packed into 128-lane rows. So the beam
    and gather kernels view ``vectors``, ``scales`` and ``neighbors0``
    without copying them, the greedy descent gathers upper-layer rows
    without re-laying out a layer (an [N, M] slice would sit lane-padded
    to 128 lanes), and dirty rows scatter straight into the packed form.
    """
    vectors: jax.Array      # [N, D] storage dtype (normalised if cosine)
    neighbors0: PackedRows  # [N, 2M] int32 (-1 pad), packed [N/R, 128]
    upper: PackedRows       # [L·N, M] int32 (-1 pad): layer l >= 1, node
                            # i at row (l-1)·N + i; L may be 0
    levels: jax.Array       # [N] int32
    entry: jax.Array        # scalar int32
    deleted: jax.Array      # [N] bool tombstones
    max_level: int          # static
    metric: str             # static
    scales: jax.Array | None = None   # [N] f32 decode scales (int8 codec)

    def tree_flatten(self):
        return ((self.vectors, self.neighbors0, self.upper, self.levels,
                 self.entry, self.deleted, self.scales),
                (self.max_level, self.metric))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (vectors, neighbors0, upper, levels, entry, deleted,
         scales) = children
        return cls(vectors, neighbors0, upper, levels, entry, deleted,
                   max_level=aux[0], metric=aux[1], scales=scales)

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def n_layers(self) -> int:
        """Upper layers held (L)."""
        return self.upper.rows // self.n

    def fits(self, g: HNSWGraph) -> bool:
        """True when this graph is the device layout of host graph ``g``'s
        capacity view, so dirty rows can scatter into it."""
        return (self.n == device_capacity(g.vectors.shape[0])
                and self.vectors.shape[1] == g.vectors.shape[1]
                and self.n_layers == g.upper.shape[0])


def _pad_rows(x: np.ndarray, cap: int, fill, axis: int = 0) -> np.ndarray:
    """Host array padded with ``fill`` to ``cap`` entries along ``axis``."""
    pad = cap - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)


def _device_deleted(deleted: np.ndarray, n: int, cap: int) -> jax.Array:
    """Tombstones for the first ``n`` rows; rows past them are dead."""
    return jnp.asarray(_pad_rows(np.asarray(deleted[:n], bool), cap, True))


def to_device_graph(g: HNSWGraph, deleted: np.ndarray | None = None,
                    enc: np.ndarray | None = None,
                    scales: np.ndarray | None = None) -> DeviceGraph:
    """Full host->device conversion (the from-scratch path; incremental
    updates go through :func:`apply_row_updates`). The host arrays are
    padded to ``device_capacity`` rows and the adjacency packed here, on
    the host, once.

    ``enc``/``scales``: codec-encoded rows to upload INSTEAD of the host
    f32 vectors (same [N, D] capacity view, DESIGN.md §9)."""
    n = g.vectors.shape[0]
    cap = device_capacity(n)
    if deleted is None:
        deleted = np.zeros(n, bool)
    v = g.vectors if enc is None else enc
    dispatch.bump("hnsw.h2d_bytes",
                  n * (v.shape[1] * (4 if enc is None else v.itemsize)
                       + 4 * g.neighbors0.shape[1]
                       + 4 * g.upper.shape[0] * (g.upper.shape[2]
                                                 if g.upper.shape[0] else 0)
                       + 4 + (4 if scales is not None else 0)))
    nbr = pack_rows(_pad_rows(np.asarray(g.neighbors0, np.int32), cap, -1))
    up = _pad_rows(np.asarray(g.upper, np.int32), cap, -1, axis=1)
    up = pack_rows(up.reshape(-1, up.shape[2]))
    return DeviceGraph(
        vectors=jnp.asarray(_pad_rows(
            np.asarray(g.vectors, np.float32) if enc is None else enc,
            cap, 0)),
        neighbors0=PackedRows(jnp.asarray(nbr.table), nbr.width),
        upper=PackedRows(jnp.asarray(up.table), up.width),
        levels=jnp.asarray(_pad_rows(np.asarray(g.levels, np.int32), cap, 0)),
        entry=jnp.asarray(max(g.entry, 0), jnp.int32),
        deleted=_device_deleted(deleted, n, cap),
        max_level=int(g.max_level),
        metric=g.metric,
        scales=(None if scales is None else jnp.asarray(
            _pad_rows(np.asarray(scales, np.float32), cap, 0))),
    )


def _set_upper(upper: PackedRows, n: int, rows, u_new) -> PackedRows:
    """Rows ``rows`` of every upper layer set to ``u_new`` [L, K, M]."""
    layers = upper.rows // n
    if not layers:
        return upper
    ids = (jnp.arange(layers, dtype=jnp.int32)[:, None] * n
           + rows[None, :]).reshape(-1)
    return upper.set_rows(ids, u_new.reshape(-1, upper.width))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_rows_jit(vectors, neighbors0, upper, levels,
                      rows, v_new, n0_new, u_new, l_new):
    """Donated in-place row scatter: the resident buffers are updated
    without a whole-buffer copy (O(|rows|) work, not O(N))."""
    vectors = vectors.at[rows].set(v_new)
    neighbors0 = neighbors0.set_rows(rows, n0_new)
    upper = _set_upper(upper, neighbors0.rows, rows, u_new)
    levels = levels.at[rows].set(l_new)
    return vectors, neighbors0, upper, levels


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _scatter_rows_scaled_jit(vectors, scales, neighbors0, upper, levels,
                             rows, v_new, s_new, n0_new, u_new, l_new):
    """Codec variant of the donated scatter: the encoded row payload and
    its per-row scale travel together (DESIGN.md §9)."""
    vectors = vectors.at[rows].set(v_new)
    scales = scales.at[rows].set(s_new)
    neighbors0 = neighbors0.set_rows(rows, n0_new)
    upper = _set_upper(upper, neighbors0.rows, rows, u_new)
    levels = levels.at[rows].set(l_new)
    return vectors, scales, neighbors0, upper, levels


def apply_row_updates(dg: DeviceGraph, g: HNSWGraph, rows,
                      deleted: np.ndarray | None = None,
                      enc: np.ndarray | None = None,
                      scales: np.ndarray | None = None) -> DeviceGraph:
    """Incremental device-graph sync (DESIGN.md §3): copy only the dirty
    ``rows`` of the host graph into the resident device tensors — O(|rows|)
    transfer + in-place donated scatter instead of a full re-upload.

    CONSUMES ``dg``: its buffers are donated to the updated graph, so the
    caller must drop its reference and use the returned DeviceGraph.
    Shapes must match (the host graph is the same capacity-padded view the
    resident graph was built from, ``DeviceGraph.fits``). ``deleted`` refreshes the tombstone
    mask; entry/max_level are always refreshed (scalar-cheap).

    ``enc``/``scales``: the codec-encoded capacity view when the resident
    graph stores encoded rows — dirty rows scatter the encoded payload
    (+ scale) instead of the f32 vectors (DESIGN.md §9).
    """
    if not dg.fits(g):
        raise ValueError("capacity/layer shape changed; full rebuild required")
    rows = np.asarray(sorted(int(r) for r in rows), np.int32)
    if rows.size:
        # pad the row set to the next power of two so the jitted scatter
        # compiles once per bucket, not once per distinct dirty-row count;
        # pad slots repeat rows[0] with identical payload (idempotent)
        bucket = 1 << (int(rows.size) - 1).bit_length()
        pad = np.full(bucket - rows.size, rows[0], np.int32)
        rp = np.concatenate([rows, pad])
        u_new = (g.upper[:, rp] if g.upper.shape[0]
                 else np.zeros((0, bucket, 1), np.int32))
        v_new = (jnp.asarray(g.vectors[rp], jnp.float32) if enc is None
                 else jnp.asarray(enc[rp]))
        dispatch.bump("hnsw.h2d_bytes",
                      bucket * (g.vectors.shape[1]
                                * (4 if enc is None else enc.itemsize)
                                + 4 * g.neighbors0.shape[1]
                                + 4 * g.upper.shape[0]
                                * (g.upper.shape[2] if g.upper.shape[0] else 0)
                                + 4 + (4 if scales is not None else 0)))
        if scales is None:
            vectors, neighbors0, upper, levels = _scatter_rows_jit(
                dg.vectors, dg.neighbors0, dg.upper, dg.levels,
                jnp.asarray(rp), v_new,
                jnp.asarray(g.neighbors0[rp], jnp.int32),
                jnp.asarray(u_new, jnp.int32),
                jnp.asarray(g.levels[rp], jnp.int32))
            dg = dataclasses.replace(dg, vectors=vectors,
                                     neighbors0=neighbors0,
                                     upper=upper, levels=levels)
        else:
            vectors, scl, neighbors0, upper, levels = \
                _scatter_rows_scaled_jit(
                    dg.vectors, dg.scales, dg.neighbors0, dg.upper,
                    dg.levels, jnp.asarray(rp), v_new,
                    jnp.asarray(scales[rp], jnp.float32),
                    jnp.asarray(g.neighbors0[rp], jnp.int32),
                    jnp.asarray(u_new, jnp.int32),
                    jnp.asarray(g.levels[rp], jnp.int32))
            dg = dataclasses.replace(dg, vectors=vectors, scales=scl,
                                     neighbors0=neighbors0, upper=upper,
                                     levels=levels)
    new_deleted = dg.deleted if deleted is None \
        else _device_deleted(deleted, g.vectors.shape[0], dg.n)
    return dataclasses.replace(
        dg, entry=jnp.asarray(max(int(g.entry), 0), jnp.int32),
        deleted=new_deleted, max_level=int(g.max_level))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_adj_jit(neighbors0, upper, rows, n0_new, u_new):
    """Donated adjacency-only scatter: bulk ingest's reciprocal connect
    touches the NEIGHBOR LISTS of up to batch·M existing rows whose
    vectors are unchanged — shipping full rows there would re-upload
    O(D) payload bytes per back-edge and erase the dirty-rows-only win
    (DESIGN.md §13). This path moves only the int32 adjacency."""
    neighbors0 = neighbors0.set_rows(rows, n0_new)
    upper = _set_upper(upper, neighbors0.rows, rows, u_new)
    return neighbors0, upper


def apply_adjacency_updates(dg: DeviceGraph, g: HNSWGraph,
                            rows) -> DeviceGraph:
    """Scatter only neighbors0/upper for the dirty ``rows`` (vectors,
    levels, scales untouched) + refresh entry/max_level. Same donation
    contract as :func:`apply_row_updates`: CONSUMES ``dg``."""
    if not dg.fits(g):
        raise ValueError("capacity/layer shape changed; full rebuild required")
    rows = np.asarray(sorted(int(r) for r in rows), np.int32)
    if rows.size:
        bucket = 1 << (int(rows.size) - 1).bit_length()
        pad = np.full(bucket - rows.size, rows[0], np.int32)
        rp = np.concatenate([rows, pad])
        u_new = (g.upper[:, rp] if g.upper.shape[0]
                 else np.zeros((0, bucket, 1), np.int32))
        dispatch.bump("hnsw.h2d_bytes",
                      bucket * 4 * (g.neighbors0.shape[1]
                                    + g.upper.shape[0]
                                    * (g.upper.shape[2]
                                       if g.upper.shape[0] else 0)))
        neighbors0, upper = _scatter_adj_jit(
            dg.neighbors0, dg.upper, jnp.asarray(rp),
            jnp.asarray(g.neighbors0[rp], jnp.int32),
            jnp.asarray(u_new, jnp.int32))
        dg = dataclasses.replace(dg, neighbors0=neighbors0, upper=upper)
    return dataclasses.replace(
        dg, entry=jnp.asarray(max(int(g.entry), 0), jnp.int32),
        max_level=int(g.max_level))


# ---------------------------------------------------------------------------
# distances
# ---------------------------------------------------------------------------
def batched_dist(metric: str, q: jax.Array, x: jax.Array) -> jax.Array:
    """q [B, D], x [B, K, D] -> [B, K] (f32 accumulate)."""
    if metric in ("cosine", "ip"):
        return 1.0 - jnp.einsum("bd,bkd->bk", q, x,
                                preferred_element_type=jnp.float32)
    d = x - q[:, None, :]
    return jnp.einsum("bkd,bkd->bk", d, d, preferred_element_type=jnp.float32)


def gather_distance(metric: str, vectors: jax.Array, q: jax.Array,
                    ids: jax.Array,
                    scales: jax.Array | None = None) -> jax.Array:
    """Fused gather(HBM)->distance: ids [B, K] (clamped), q [B, D] -> [B, K].

    On TPU this always runs kernels/gather_distance.py; off-TPU the jnp
    reference keeps identical semantics (invalid ids must be masked by
    the caller).
    ``scales`` fuses the codec decode into the distance (DESIGN.md §9).
    """
    from repro.kernels import ops
    return ops.gather_distance(vectors, q, ids, metric=metric, scales=scales)


def _prep_queries(g: DeviceGraph, queries) -> jax.Array:
    q = jnp.asarray(queries, jnp.float32)
    if q.ndim == 1:
        q = q[None]
    if g.metric == "cosine":
        q = q / jnp.maximum(
            jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    return q


# ---------------------------------------------------------------------------
# upper-layer greedy descent (all queries lock-step)
# ---------------------------------------------------------------------------
def _greedy_layer(g: DeviceGraph, q: jax.Array, ep: jax.Array,
                  ep_dist: jax.Array, layer: int) -> tuple[jax.Array, jax.Array]:
    """One layer's greedy descent. ep/ep_dist [B]. Static layer index."""
    def cond(state):
        _, _, improved = state
        return jnp.any(improved)

    def body(state):
        ep, ep_dist, _ = state
        nbrs = g.upper.take((layer - 1) * g.n + ep)            # [B, M]
        valid = nbrs >= 0
        ids = jnp.clip(nbrs, 0, g.n - 1)
        d = gather_distance(g.metric, g.vectors, q, ids, g.scales)
        d = jnp.where(valid, d, INF)
        j = jnp.argmin(d, axis=-1)
        best_d = jnp.take_along_axis(d, j[:, None], 1)[:, 0]
        best_i = jnp.take_along_axis(ids, j[:, None], 1)[:, 0]
        improved = best_d < ep_dist
        return (jnp.where(improved, best_i, ep),
                jnp.where(improved, best_d, ep_dist),
                improved)

    init = (ep, ep_dist, jnp.ones_like(ep, bool))
    ep, ep_dist, _ = jax.lax.while_loop(cond, body, init)
    return ep, ep_dist


# ---------------------------------------------------------------------------
# layer-0 beam search
# ---------------------------------------------------------------------------
def _beam_search(g: DeviceGraph, q: jax.Array, ep: jax.Array,
                 ep_dist: jax.Array, ef: int, max_iters: int | None = None):
    """ef-beam best-first search on layer 0. Returns sorted (ids, dists)."""
    b = q.shape[0]
    m2 = g.neighbors0.width
    # explicit None check: max_iters=0 means ZERO expansions (entry point
    # only), not "default to ef"
    max_iters = ef if max_iters is None else max_iters

    beam_d = jnp.full((b, ef), INF).at[:, 0].set(ep_dist)
    beam_i = jnp.full((b, ef), -1, jnp.int32).at[:, 0].set(ep)
    beam_x = jnp.zeros((b, ef), bool)                    # expanded?

    def cond(state):
        beam_d, beam_i, beam_x, it = state
        frontier = (~beam_x) & (beam_i >= 0)
        return jnp.logical_and(it < max_iters, jnp.any(frontier))

    def body(state):
        beam_d, beam_i, beam_x, it = state
        # best unexpanded candidate per query
        cand_d = jnp.where(beam_x | (beam_i < 0), INF, beam_d)
        j = jnp.argmin(cand_d, axis=-1)                      # [B]
        has = jnp.take_along_axis(cand_d, j[:, None], 1)[:, 0] < INF
        cur = jnp.take_along_axis(beam_i, j[:, None], 1)[:, 0]
        beam_x = beam_x.at[jnp.arange(b), j].set(beam_x[jnp.arange(b), j] | has)
        # expand: gather 2M neighbors + distances
        nbrs = g.neighbors0.take(jnp.clip(cur, 0, g.n - 1))
        valid = (nbrs >= 0) & has[:, None]
        ids = jnp.clip(nbrs, 0, g.n - 1)
        d = gather_distance(g.metric, g.vectors, q, ids, g.scales)
        d = jnp.where(valid, d, INF)
        # merge into beam: two-key sort then adjacent-dup masking
        all_d = jnp.concatenate([beam_d, d], axis=1)         # [B, ef+2M]
        all_i = jnp.concatenate([beam_i, ids], axis=1)
        all_x = jnp.concatenate(
            [beam_x, jnp.zeros((b, m2), bool)], axis=1)
        all_i = jnp.where(all_d >= INF, -1, all_i)
        sd, si, sx = jax.lax.sort((all_d, all_i, all_x), num_keys=2)
        dup = jnp.concatenate(
            [jnp.zeros((b, 1), bool), (si[:, 1:] == si[:, :-1]) & (si[:, 1:] >= 0)],
            axis=1)
        sd = jnp.where(dup, INF, sd)
        sx = jnp.where(dup, True, sx)
        sd, si, sx = jax.lax.sort((sd, si, sx), num_keys=2)
        return (sd[:, :ef], si[:, :ef], sx[:, :ef], it + 1)

    beam_d, beam_i, beam_x, _ = jax.lax.while_loop(
        cond, body, (beam_d, beam_i, beam_x, jnp.zeros((), jnp.int32)))
    return beam_i, beam_d


def _beam_search_fused(g: DeviceGraph, q: jax.Array, ep: jax.Array,
                       ep_dist: jax.Array, ef: int,
                       max_iters: int | None = None,
                       expand_t: int | None = None):
    """One-launch layer-0 beam search (kernels/beam_search.py via
    ops.beam_search): the whole ef-beam — neighbor gather, fused codec
    decode, bitonic merge — runs in a single kernel, expanding the top-T
    frontier nodes per hop. The jnp fallback off-TPU runs the identical
    algorithm (``ref.beam_search_ref``)."""
    from repro.kernels import ops
    return ops.beam_search(
        g.vectors, g.neighbors0, q, ep, ep_dist, ef=ef, metric=g.metric,
        scales=g.scales,
        expand_t=DEFAULT_EXPAND_T if expand_t is None else expand_t,
        max_iters=max_iters)


def descend(g: DeviceGraph, q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Greedy descent from the entry point through every upper layer:
    prepped queries [B, D] -> layer-0 entry points (ep [B], ep_dist [B])."""
    ep = jnp.broadcast_to(g.entry, q.shape[:1])
    x0 = jnp.take(g.vectors, ep, axis=0)
    if g.scales is not None:                 # decode the entry row (§9)
        x0 = x0.astype(jnp.float32) * jnp.take(g.scales, ep)[:, None]
    ep_dist = batched_dist(g.metric, q, x0[:, None])[:, 0]
    for layer in range(g.max_level, 0, -1):      # static unroll (few layers)
        ep, ep_dist = _greedy_layer(g, q, ep, ep_dist, layer)
    return ep, ep_dist


def search_core(g: DeviceGraph, q: jax.Array, k: int, ef: int,
                max_iters: int | None = None, beam_impl: str = "fused",
                beam_expand: int | None = None):
    """Traceable whole-search body (descent + beam + tombstone filter),
    shared by the single-graph jit below and the stacked segment fan-out
    (core/stacked.py), which calls it per-shard inside ``shard_map``.
    Queries must already be prepped (``_prep_queries``).

    ``beam_impl`` selects the layer-0 beam: "fused" (default) runs the
    whole beam as one kernel launch (DESIGN.md §12); "jnp" is the
    per-hop ``while_loop`` reference. ``beam_expand`` overrides the
    fused path's per-hop expansion width (default DEFAULT_EXPAND_T)."""
    if beam_impl not in ("fused", "jnp"):
        raise ValueError(f"unknown beam_impl {beam_impl!r}; "
                         "expected 'fused' or 'jnp'")
    with jax.named_scope("hnsw.descent"):
        ep, ep_dist = descend(g, q)
    with jax.named_scope("hnsw.beam"):
        if beam_impl == "fused":
            beam_i, beam_d = _beam_search_fused(g, q, ep, ep_dist, ef,
                                                max_iters, beam_expand)
        else:
            beam_i, beam_d = _beam_search(g, q, ep, ep_dist, ef,
                                          max_iters)
    # tombstone filter: deleted rows were traversable during the beam search
    # but must not be returned (DESIGN.md §3)
    dead = jnp.take(g.deleted, jnp.clip(beam_i, 0, g.n - 1)) | (beam_i < 0)
    beam_d = jnp.where(dead, INF, beam_d)
    beam_i = jnp.where(dead, -1, beam_i)
    beam_d, beam_i = jax.lax.sort((beam_d, beam_i), num_keys=1,
                                  is_stable=True)
    return beam_i[:, :k], beam_d[:, :k]


@functools.partial(jax.jit, static_argnames=("k", "ef", "max_iters",
                                             "beam_impl", "beam_expand"))
def _search_jit(g: DeviceGraph, q: jax.Array, k: int, ef: int,
                max_iters: int | None, beam_impl: str,
                beam_expand: int | None):
    return search_core(g, q, k, ef, max_iters, beam_impl, beam_expand)


def search_graph(g: DeviceGraph, queries, k: int = 10, ef: int = 64,
                 max_iters: int | None = None, beam_impl: str = "fused",
                 beam_expand: int | None = None):
    """Batched k-NN query. queries [B, D] (or [D]) -> (ids [B,k], dist [B,k]).

    ``beam_impl``/``beam_expand``: layer-0 beam selection, see
    ``search_core``. Launch economics are counted host-side
    (core/dispatch.py): one fused beam launch vs O(ef) per-hop
    dispatches on the jnp path."""
    q = _prep_queries(g, queries)
    ef = max(ef, k)
    dispatch.bump("hnsw.beam_launches",
                  dispatch.beam_launches(beam_impl, ef, max_iters))
    return _search_jit(g, q, k, ef, max_iters, beam_impl, beam_expand)


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean fraction of true k-NN recovered.

    Vectorized broadcast membership (set semantics: duplicate found ids
    count once, duplicate true ids count once — parity with the old
    per-row Python set loop, without O(B·k) interpreter work inside
    benchmark hot loops)."""
    f = np.asarray(found_ids)
    t = np.asarray(true_ids)
    if t.size == 0:
        return 0.0
    member = (t[:, :, None] == f[:, None, :]).any(axis=2)      # [B, K]
    # count each distinct true id once per row (first occurrence)
    k = t.shape[1]
    dup = ((t[:, :, None] == t[:, None, :])
           & (np.arange(k)[None, :, None] > np.arange(k)[None, None, :]))
    member &= ~dup.any(axis=2)
    return float(member.sum()) / max(t.size, 1)
