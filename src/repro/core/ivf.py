"""IVF-Flat index — beyond-paper ANN backend (the paper cites PQ/FAISS-style
coarse quantisation as the other major ANN family; IVF is its TPU-friendly
core: fixed-shape gathers + the same fused distance kernels as HNSW).

Build: a few Lloyd iterations of k-means (pure jnp) -> ``nlist`` centroids;
rows go into fixed-capacity inverted lists (padded, -1). Search: score the
query against centroids, take ``nprobe`` lists, gather their rows (one
``gather_distance`` wave per query batch), exact top-k over candidates.
Everything is fixed-shape, so the whole query path jit-compiles once.

Sharded operation (DESIGN.md §8): the coarse quantiser is GLOBAL (trained
once over all live rows, replicated to every shard — it is canonical
state), while the inverted lists and row payloads are PER-SHARD: each
shard keeps lists over its own hash-routed rows, probes the same
``nprobe`` clusters as every other shard, scores only its local
candidates (``nprobe * cap / S`` distance work per device), and the
per-shard top-k merges through the hierarchical tree. The union of the
shards' probed candidates is exactly the 1-shard candidate set, which is
why shard count does not change results.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.codec import (check_codec_arrays as _check_codec_arrays,
                              effective_rerank, get_codec, rerank_exact)
from repro.core.hnsw_build import normalize_rows
from repro.core.index import VectorIndex
from repro.core.sharded import (SHARD_AXIS, ShardedRows, hierarchical_topk,
                                resolve_wire_bf16, trim_merge_width)
from repro.kernels import ops


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class IVFIndex:
    vectors: jax.Array        # [N, D] (normalised if cosine); may be
                              # codec-encoded (DESIGN.md §9)
    centroids: jax.Array      # [nlist, D] always fp32 (trained state)
    lists: jax.Array          # [nlist, cap] int32, -1 padded
    metric: str
    scales: jax.Array | None = None   # [N] per-row decode scales (int8)

    def tree_flatten(self):
        return ((self.vectors, self.centroids, self.lists, self.scales),
                (self.metric,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        vectors, centroids, lists, scales = children
        return cls(vectors=vectors, centroids=centroids, lists=lists,
                   metric=aux[0], scales=scales)

    @property
    def n(self):
        return self.vectors.shape[0]


def kmeans(x: jnp.ndarray, k: int, iters: int = 8, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    init = jax.random.choice(key, x.shape[0], (k,), replace=False)
    cent = x[init]

    def step(cent, _):
        d = (jnp.sum(x * x, 1)[:, None] - 2 * x @ cent.T
             + jnp.sum(cent * cent, 1)[None, :])
        assign = jnp.argmin(d, 1)
        sums = jax.ops.segment_sum(x, assign, k)
        cnt = jax.ops.segment_sum(jnp.ones(x.shape[0]), assign, k)
        new = jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt[:, None], 1),
                        cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    d = (jnp.sum(x * x, 1)[:, None] - 2 * x @ cent.T
         + jnp.sum(cent * cent, 1)[None, :])
    return cent, jnp.argmin(d, 1)


def build_ivf(vectors, *, nlist: int = 64, metric: str = "cosine",
              iters: int = 8, seed: int = 0) -> IVFIndex:
    v = np.asarray(vectors, np.float32)
    if metric == "cosine":
        v = normalize_rows(v)
    vj = jnp.asarray(v)
    cent, assign = kmeans(vj, nlist, iters, seed)
    assign = np.asarray(assign)
    counts = np.bincount(assign, minlength=nlist)
    cap = int(counts.max())
    lists = np.full((nlist, cap), -1, np.int32)
    cursor = np.zeros(nlist, np.int64)
    for i, a in enumerate(assign):
        lists[a, cursor[a]] = i
        cursor[a] += 1
    return IVFIndex(vectors=vj, centroids=cent,
                    lists=jnp.asarray(lists), metric=metric)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _search(idx: IVFIndex, q: jax.Array, k: int, nprobe: int):
    b = q.shape[0]
    cap = idx.lists.shape[1]
    # coarse: nearest nprobe centroids
    cd = ops.gather_distance(
        idx.centroids, q,
        jnp.broadcast_to(jnp.arange(idx.centroids.shape[0]),
                         (b, idx.centroids.shape[0])), metric=idx.metric)
    _, probe = jax.lax.top_k(-cd, nprobe)                 # [B, nprobe]
    cand = jnp.take(idx.lists, probe, axis=0).reshape(b, nprobe * cap)
    valid = cand >= 0
    ids = jnp.clip(cand, 0, idx.n - 1)
    d = ops.gather_distance(idx.vectors, q, ids, metric=idx.metric,
                            scales=idx.scales)
    d = jnp.where(valid, d, jnp.float32(3e38))
    neg, j = jax.lax.top_k(-d, k)
    out_ids = jnp.take_along_axis(ids, j, axis=1)
    # list-padding slots that reached the top-k (fewer live candidates
    # than k) must not leak a clipped row id: mark them missing
    out_ids = jnp.where(-neg >= jnp.float32(3e38), -1, out_ids)
    return out_ids, -neg


def search_ivf(idx: IVFIndex, queries, k: int = 10, nprobe: int = 8):
    q = jnp.asarray(queries, jnp.float32)
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    if idx.metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    nprobe = min(nprobe, idx.centroids.shape[0])
    # the probed lists expose at most nprobe*cap candidates; top_k cannot
    # take more than that — callers pad the shortfall (protocol: k slots)
    k = min(k, nprobe * idx.lists.shape[1])
    ids, dists = _search(idx, q, k, nprobe)
    if squeeze:
        return ids[0], dists[0]
    return ids, dists


# ---------------------------------------------------------------------------
# sharded probe: per-shard lists, global centroids, hierarchical merge
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _ivf_fanout_fn(mesh, k: int, nprobe: int, metric: str,
                   has_scales: bool = False, wire_bf16: bool = False):
    """Compiled sharded IVF search. blocks [S,R,D] + lists [S,nlist,cap] +
    gids [S,R] (and, for a scaled codec, scales [S,R]) sharded over
    ``"shard"``; centroids [nlist,D] and queries [B,D] replicated ->
    (dists [B,k], global row ids [B,k]) replicated. Every shard probes
    the SAME clusters (the coarse score is replicated arithmetic on
    replicated fp32 centroids), gathers only its local members — decoding
    codec rows inside the fused kernel (DESIGN.md §9) — and the per-shard
    top-k merges through the hierarchical tree."""
    INF = jnp.float32(3e38)

    def local(blk, lists, gid, cent, q, scl=None):
        blk, lists, gid = blk[0], lists[0], gid[0]
        b = q.shape[0]
        nlist, cap = lists.shape
        r = blk.shape[0]
        cd = ops.gather_distance(
            cent, q, jnp.broadcast_to(jnp.arange(nlist), (b, nlist)),
            metric=metric)
        _, probe = jax.lax.top_k(-cd, nprobe)             # [B, nprobe]
        cand = jnp.take(lists, probe, axis=0).reshape(b, nprobe * cap)
        valid = cand >= 0
        slots = jnp.clip(cand, 0, r - 1)
        d = ops.gather_distance(blk, q, slots, metric=metric,
                                scales=None if scl is None else scl[0])
        d = jnp.where(valid, d, INF)
        g = jnp.take(gid, slots)
        d, g = trim_merge_width(d, g, k, INF)
        g = jnp.where(d >= INF, -1, g)
        return hierarchical_topk(d, g, k, (SHARD_AXIS,),
                                 wire_bf16=wire_bf16, tie_break_ids=True,
                                 axis_sizes=(mesh.shape[SHARD_AXIS],))

    if has_scales:
        fn = shard_map(
            lambda blk, lists, gid, scl, cent, q:
                local(blk, lists, gid, cent, q, scl),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None),
                      P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                      P(None, None), P(None, None)),
            out_specs=(P(None, None), P(None, None)),
            check_vma=False)
        return jax.jit(fn)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(SHARD_AXIS, None, None),
                             P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                             P(None, None), P(None, None)),
                   out_specs=(P(None, None), P(None, None)),
                   check_vma=False)
    return jax.jit(fn)


class IVFVectorIndex(VectorIndex):
    """Keyed mutable IVF backend (DESIGN.md §1/§4/§8).

    Centroids are trained once (k-means over the rows present at the first
    query); later inserts are assigned to their nearest existing centroid —
    classic IVF ``add`` semantics. Deletes drop the row from its inverted
    list at the next device pack (no tombstone needed in the search path
    because packing already excludes dead rows). The packed device index is
    rebuilt lazily after mutations.

    Because centroids are *trained-once* state that depends on when the
    first query ran (not only on the mutation history), training emits a
    ``derived.centroids`` WAL record when a store is attached — WAL replay
    then reproduces the exact centroids, keeping a warm restore bit-for-bit
    equal to the live index (DESIGN.md §7).

    With ``n_shards > 1`` storage and routing live in ``ShardedRows``;
    the centroids stay global (canonical state, so ``state_dict`` is
    identical at any shard count) while each shard packs inverted lists
    over its own rows and searches them locally (DESIGN.md §8).
    """

    kind = "ivf"

    def __init__(self, *, metric: str = "cosine", dim: int | None = None,
                 nlist: int = 64, nprobe: int = 8, iters: int = 8,
                 seed: int = 0, n_shards: int = 1, dtype: str = "fp32",
                 rerank_factor: int | None = None):
        if metric not in ("cosine", "ip", "l2"):
            raise ValueError(f"unknown metric {metric!r}")
        self.metric = metric
        self.dim = dim
        self.nlist = nlist
        self.nprobe = nprobe
        self.iters = iters
        self.seed = seed
        self.n_shards = int(n_shards)
        self.dtype = str(dtype)
        self.rerank_factor = rerank_factor
        self._codec = get_codec(self.dtype)
        # rows are normalised at INSERT time for cosine (classic IVF add
        # semantics), so the substrate packs them raw — and under a lossy
        # codec quantizes the already-normalized rows once at ingest
        # (DESIGN.md §9)
        self._rows = ShardedRows(n_shards=self.n_shards, metric=metric,
                                 dim=dim, normalize_on_pack=False,
                                 codec=self._codec)
        self._centroids: np.ndarray | None = None   # trained lazily
        self._idx: IVFIndex | None = None           # S==1 packed device index
        self._live_rows: np.ndarray | None = None   # S==1 pack order
        self._spack = None                          # S>1 sharded pack

    # ------------------------------------------------------------ mutation
    def _invalidate(self) -> None:
        self._idx = None
        self._live_rows = None
        self._spack = None

    def _insert_impl(self, key: str, value: np.ndarray) -> None:
        v = np.asarray(value, np.float32).reshape(-1)
        if self.metric == "cosine":
            v = v / max(float(np.linalg.norm(v)), 1e-12)
        self._rows.upsert(key, v)
        self.dim = self._rows.dim
        self._invalidate()
        self._bump_epoch()

    def _bulk_insert_impl(self, keys: list[str], values: np.ndarray) -> None:
        values = np.asarray(values, np.float32)
        if self.metric == "cosine":
            values = normalize_rows(values)
        self._rows.upsert_many(keys, values)
        self.dim = self._rows.dim
        self._invalidate()
        self._bump_epoch()

    def _update_impl(self, key: str, value: np.ndarray) -> None:
        self._insert_impl(key, value)

    def _delete_impl(self, key: str) -> None:
        self._rows.tombstone(key)
        self._invalidate()
        self._bump_epoch()

    def _compact_impl(self) -> None:
        """Physically drop tombstoned rows (DESIGN.md §7). Centroids are
        dropped too — they are aggregates over data that may include the
        deleted rows (a singleton cluster's centroid IS the deleted
        vector) — and retrain over live rows at the next pack."""
        self._rows.compact()
        self._centroids = None
        self._invalidate()
        self._bump_epoch()

    # ----------------------------------------------------------- training
    def _coarse(self, live: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
        """-> (centroids, assignment over live rows, nlist). Shared by the
        single-device and sharded packs so the quantiser (and therefore
        the candidate sets) is identical at any shard count."""
        v = self._rows.vectors[live]
        nlist = min(self.nlist, live.size)
        if self._centroids is None or self._centroids.shape[0] != nlist:
            cent, assign = kmeans(jnp.asarray(v), nlist, self.iters, self.seed)
            self._centroids = np.asarray(cent)
            assign = np.asarray(assign)
            # derived-state journaling (DESIGN.md §7): training happened at
            # query time, outside the mutation history, so replay alone
            # cannot reproduce it — log the trained centroids so a warm
            # restore lands on the exact same coarse quantiser
            if self._store is not None:
                self._store.wal_append("derived.centroids",
                                       epoch=self._epoch, meta={},
                                       arrays={"centroids": self._centroids})
        else:
            d = (np.sum(v * v, 1)[:, None] - 2 * v @ self._centroids.T
                 + np.sum(self._centroids ** 2, 1)[None, :])
            assign = np.argmin(d, 1)
        return self._centroids, assign, nlist

    # --------------------------------------------------------------- query
    def _pack(self) -> IVFIndex:
        """(Re)build the single-device padded lists over live rows only."""
        if self._idx is not None:
            return self._idx
        live = np.flatnonzero(self._rows.alive)
        if live.size == 0:
            raise ValueError("index is empty")
        self._live_rows = live
        cent, assign, nlist = self._coarse(live)
        counts = np.bincount(assign, minlength=nlist)
        cap = max(int(counts.max()), 1)
        lists = np.full((nlist, cap), -1, np.int32)
        cursor = np.zeros(nlist, np.int64)
        for i, a in enumerate(assign):
            lists[a, cursor[a]] = i
            cursor[a] += 1
        if self._codec.lossy:
            # device payload = canonical encoded rows; the fine distance
            # decodes in-kernel (asymmetric, DESIGN.md §9)
            vecs = jnp.asarray(self._rows.encoded[live])
            scl = (jnp.asarray(self._rows.scales[live])
                   if self._rows.scales is not None else None)
        else:
            vecs, scl = jnp.asarray(self._rows.vectors[live]), None
        self._idx = IVFIndex(vectors=vecs, centroids=jnp.asarray(cent),
                             lists=jnp.asarray(lists), metric=self.metric,
                             scales=scl)
        return self._idx

    def _pack_sharded(self):
        """(Re)build the per-shard inverted lists (DESIGN.md §8): every
        live row's slot joins its cluster's list ON ITS OWNING SHARD."""
        if self._spack is not None:
            return self._spack
        live = np.flatnonzero(self._rows.alive)
        if live.size == 0:
            raise ValueError("index is empty")
        mesh, blocks, gids, scl = self._rows.pack()
        cent, assign, nlist = self._coarse(live)
        s_lists: list[list[list[int]]] = [
            [[] for _ in range(nlist)] for _ in range(self.n_shards)]
        counts = np.bincount(assign, minlength=nlist)
        cap_global = max(int(counts.max()), 1)    # 1-shard-equivalent cap:
        cap = 1                                   # keeps the k clamp equal
        for rank, row in enumerate(live):
            s, slot = self._rows.placement_of_row(int(row))
            bucket = s_lists[s][int(assign[rank])]
            bucket.append(slot)
            cap = max(cap, len(bucket))
        lists = np.full((self.n_shards, nlist, cap), -1, np.int32)
        for s in range(self.n_shards):
            for c in range(nlist):
                m = s_lists[s][c]
                lists[s, c, :len(m)] = m
        lj = jax.device_put(jnp.asarray(lists),
                            NamedSharding(mesh, P(SHARD_AXIS, None, None)))
        self._spack = (mesh, blocks, lj, gids, scl, jnp.asarray(cent),
                       nlist, cap_global, int(live.size))
        return self._spack

    def query_batch(self, queries, k: int = 10, nprobe: int | None = None,
                    **kw):
        """One fixed-shape probed search for the whole [B, D] batch —
        single-dispatch sharded fan-out when ``n_shards > 1``.

        Under a lossy codec (DESIGN.md §9) the probed candidates are
        scored asymmetrically (fp32 query vs encoded rows, decode fused
        in-kernel), the search over-fetches ``k·rerank_factor``, and the
        survivors rerank exactly in fp32 from the canonical host rows.

        Extra search kwargs from other backends (e.g. hnsw's ``ef``) are
        accepted and ignored so the serving layer can pass one knob set
        through any backend."""
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_batch expects [B, D], got {q.shape}")
        rf = effective_rerank(self._codec, self.rerank_factor)
        from repro.core.flat import _pad_results
        if self.n_shards == 1:
            idx = self._pack()
            ids, d = search_ivf(idx, q, k=min(k * rf, idx.n),
                                nprobe=nprobe or self.nprobe)
            ids, d = np.asarray(ids), np.asarray(d)
            if rf > 1:
                gids = np.where(ids >= 0, self._live_rows[ids], -1)
                d, gids = self._rows.rerank_topk(q, gids, k)
                return _pad_results(
                    [[self._rows.key_of_row(int(r)) if r >= 0 else None
                      for r in row] for row in gids], d, k)
            return _pad_results(
                [[self._rows.key_of_row(int(self._live_rows[j]))
                  if j >= 0 else None for j in row] for row in ids], d, k)
        mesh, blocks, lists, gids, scl, cent, nlist, cap_global, n_live = \
            self._pack_sharded()
        qj = jnp.asarray(q)
        if self.metric == "cosine":
            qj = qj / jnp.maximum(
                jnp.linalg.norm(qj, axis=-1, keepdims=True), 1e-12)
        npr = min(nprobe or self.nprobe, nlist)
        # same candidate-capacity clamp the 1-shard path applies
        k_eff = min(min(k * rf, n_live), npr * cap_global)
        fn = _ivf_fanout_fn(mesh, k_eff, npr, self.metric,
                            has_scales=scl is not None,
                            wire_bf16=resolve_wire_bf16(None))
        d, g = (fn(blocks, lists, gids, scl, cent, qj) if scl is not None
                else fn(blocks, lists, gids, cent, qj))
        d, g = np.asarray(d), np.asarray(g)
        if rf > 1:
            d, g = self._rows.rerank_topk(q, g, k)
        return _pad_results(
            [[self._rows.key_of_row(int(r)) if r >= 0 else None
              for r in row] for row in g], d, k)

    def exact_query(self, query, k: int = 10):
        # nprobe = nlist probes every list -> exact over the live set
        if self.n_shards == 1:
            idx = self._pack()
            return self.query(query, k, nprobe=idx.centroids.shape[0])
        nlist = self._pack_sharded()[6]
        return self.query(query, k, nprobe=nlist)

    # --------------------------------------------------------- persistence
    # Canonical state only (DESIGN.md §8): vectors + tombstones + keys +
    # the GLOBAL centroids — per-shard lists are derived pack state, so
    # the same state_dict restores onto any shard count.
    def config_dict(self) -> dict:
        return {"metric": self.metric, "dim": self.dim, "nlist": self.nlist,
                "nprobe": self.nprobe, "iters": self.iters,
                "seed": self.seed, "n_shards": self.n_shards,
                "dtype": self.dtype, "rerank_factor": self.rerank_factor}

    def state_dict(self) -> tuple[dict, dict]:
        cent = (self._centroids if self._centroids is not None
                else np.zeros((0, self.dim or 0), np.float32))
        if self._codec.lossy:
            arrays = {"vectors_enc":
                      self._codec.to_storage(self._rows.encoded),
                      "alive": self._rows.alive, "centroids": cent}
            if self._rows.scales is not None:
                arrays["scales"] = self._rows.scales
        else:
            arrays = {"vectors": self._rows.vectors,
                      "alive": self._rows.alive, "centroids": cent}
        meta = {"keys": list(self._rows.key_list), "epoch": self._epoch,
                "has_centroids": self._centroids is not None}
        return arrays, meta

    def restore_state(self, arrays: dict, meta: dict) -> None:
        _check_codec_arrays(self._codec, arrays, self.kind)
        if self._codec.lossy:
            self._rows.restore_encoded(arrays["vectors_enc"],
                                       arrays.get("scales"),
                                       list(meta["keys"]),
                                       np.asarray(arrays["alive"], bool))
        else:
            self._rows.restore(np.asarray(arrays["vectors"], np.float32),
                               list(meta["keys"]),
                               np.asarray(arrays["alive"], bool))
        if self._rows.dim:
            self.dim = self._rows.dim
        self._centroids = (np.asarray(arrays["centroids"], np.float32)
                           if meta["has_centroids"] else None)
        self._epoch = int(meta["epoch"])
        self._invalidate()

    def _apply_derived(self, op: str, meta: dict, arrays: dict) -> None:
        if op != "derived.centroids":
            raise ValueError(f"IVFVectorIndex cannot replay {op!r}")
        self._centroids = np.asarray(arrays["centroids"], np.float32)
        self._invalidate()

    def _row_count(self) -> int:
        return self._rows.row_count

    @property
    def size(self) -> int:
        return self._rows.size

    def _contains(self, key: str) -> bool:
        return self._rows.contains(key)

    def keys(self) -> list[str]:
        return self._rows.live_keys()

    @property
    def shard_count(self) -> int:
        return self.n_shards

    def shard_stats(self) -> list[dict]:
        return self._rows.shard_stats()
