"""Shard-aware row substrate: the mesh owns the corpus (DESIGN.md §8).

Before this layer, every ``VectorIndex`` backend stored its rows and ran
its search on a single device, while the pod-scale path
(``core/distributed.sharded_flat_topk``) only worked on a static array
with no CRUD. ``ShardedRows`` unifies the two: it is the keyed, mutable
row store the flat and IVF backends are built on, and its search is the
general fan-out/merge primitive the static helper now delegates to.

Three layers of state:

  * **canonical** (what persists; shard-count independent): append-only
    host vectors ``[T, D]`` in insertion order, the row -> key table, and
    the ``alive`` tombstone mask. ``state arrays`` serialize ONLY this —
    a snapshot taken at 8 shards restores onto 1 (or vice versa) because
    placement is derived, not stored (DESIGN.md §8, resharding).
  * **placement** (derived): deterministic key->shard routing
    (``shard_of_key``: stable blake2b, never Python ``hash``) plus
    per-shard slot tables with free-slot reuse — a tombstoned row's slot
    is handed to the next insert routed to the same shard, so block
    shapes stay put under mutation churn (same motivation as the HNSW
    capacity padding, DESIGN.md §3).
  * **device** (lazy): row blocks ``[S, R, D]`` + global-id map
    ``[S, R]`` placed with ``NamedSharding`` over the ``"shard"`` mesh
    axis. Queries are replicated; each shard runs the fused
    ``flat_topk`` kernel over its own block and the per-shard top-k
    merges through the existing ``hierarchical_topk`` tree
    (distributed/collectives.py) — one log-depth reduction.

Single-shard indexes (``n_shards=1``, the default) bypass the mesh
machinery entirely and run the exact same single-device code path as
before this layer existed — bit-for-bit, which is what lets the whole
pre-existing test suite double as the sharded path's parity oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.codec import VectorCodec, get_codec, rerank_exact
from repro.core.dispatch import span
from repro.core.hnsw_build import normalize_rows
from repro.distributed.collectives import hierarchical_topk
from repro.kernels import ops

INF = np.float32(3e38)
SHARD_AXIS = "shard"


def resolve_wire_bf16(flag: bool | None) -> bool:
    """Resolve a per-call/per-index ``wire_bf16`` knob: explicit values
    win; None falls back to the REPRO_WIRE_BF16 env toggle (off by
    default — bf16 wire halves merge bytes but costs bitwise parity with
    the 1-shard path, so it is opt-in)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_WIRE_BF16", "0") == "1"
# re-layout the slot tables when free (tombstoned/reusable) slots exceed
# this fraction of block capacity: bounds the scan work spent on free slots
REPACK_FREE_FRACTION = 0.25


def shard_of_key(key: str, n_shards: int) -> int:
    """Deterministic key -> owning shard. Stable across processes and
    restarts (blake2b, NOT Python ``hash``): the WAL replays mutations
    through the same routing the live index used, and a resharded
    restore re-derives placement from keys alone."""
    if n_shards <= 1:
        return 0
    h = hashlib.blake2b(key.encode("utf-8"), digest_size=8).digest()
    return int.from_bytes(h, "little") % n_shards


def ensure_shard_devices(n_shards: int) -> None:
    """Raise early (with the CPU-simulation recipe) when the process
    cannot place ``n_shards`` shards."""
    n_dev = len(jax.devices())
    if n_shards > n_dev:
        raise ValueError(
            f"n_shards={n_shards} needs {n_shards} devices, found {n_dev}; "
            "on CPU simulate with XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n_shards} (set before importing jax)")


@functools.lru_cache(maxsize=8)
def shard_mesh(n_shards: int) -> Mesh:
    """1-D mesh over the first ``n_shards`` devices, axis ``"shard"``."""
    ensure_shard_devices(n_shards)
    return jax.make_mesh((n_shards,), (SHARD_AXIS,),
                         devices=jax.devices()[:n_shards])


# ---------------------------------------------------------------------------
# fan-out search: per-shard fused top-k + hierarchical merge
# ---------------------------------------------------------------------------
def trim_merge_width(d: jax.Array, ids: jax.Array, k: int, inf
                     ) -> tuple[jax.Array, jax.Array]:
    """Bring one shard's masked candidate set to exactly the k-wide merge
    format: re-select k when over-fetched, pad with (inf, -1) when the
    shard is short. Callers mask invalid candidates (free slots, DB
    padding, list padding) to distance ``inf`` BEFORE calling — this is
    the one place the local-result shape meets the merge contract, shared
    by the flat fan-out, the IVF fan-out, and the static pod-scale path
    (core/distributed.py)."""
    kk = d.shape[1]
    if kk > k:
        neg, j = jax.lax.top_k(-d, k)
        return -neg, jnp.take_along_axis(ids, j, axis=1)
    if kk < k:
        b = d.shape[0]
        d = jnp.concatenate([d, jnp.full((b, k - kk), inf, d.dtype)], axis=1)
        ids = jnp.concatenate(
            [ids, jnp.full((b, k - kk), -1, ids.dtype)], axis=1)
    return d, ids


@functools.lru_cache(maxsize=64)
def _fanout_topk_fn(mesh: Mesh, k: int, metric: str,
                    has_scales: bool = False, wire_bf16: bool = False):
    """Compiled sharded exact top-k.

    blocks [S, R, D] + gids [S, R] (sharded over ``"shard"``), queries
    [B, D] (replicated) -> (dists [B, k], global ids [B, k]) replicated.
    Blocks may be codec-encoded (DESIGN.md §9); with ``has_scales`` a
    sharded [S, R] scale table rides along and the per-row decode fuses
    into the distance kernel. Slots with gid < 0 (free slots / block
    padding) are masked inside the fused ``flat_topk`` scan, so each
    shard fetches exactly k; a shard with fewer live rows pads with
    (INF, -1).

    The merge runs the ppermute tree reduction (static axis size from
    the mesh); ``wire_bf16`` halves its distance payload per round at
    the cost of bf16-resolution ordering (ids stay exact). Cache keys
    are (mesh, k, metric, has_scales, wire_bf16): none grows with the
    corpus, so the lru_cache cannot churn across epochs.
    """
    n_shards = mesh.shape[SHARD_AXIS]

    def local(blk, gid, q, scl=None):
        blk, gid = blk[0], gid[0]
        r = blk.shape[0]
        d, i = ops.flat_topk(blk, q, min(k, r), metric=metric,
                             scales=None if scl is None else scl[0],
                             valid=gid >= 0)
        g = jnp.take(gid, i)
        d = jnp.where(g >= 0, d, jnp.float32(INF))
        d, g = trim_merge_width(d, g, k, jnp.float32(INF))
        g = jnp.where(d >= jnp.float32(INF), -1, g)
        return hierarchical_topk(d, g, k, (SHARD_AXIS,),
                                 wire_bf16=wire_bf16, tie_break_ids=True,
                                 axis_sizes=(n_shards,))

    if has_scales:
        fn = shard_map(lambda blk, gid, scl, q: local(blk, gid, q, scl),
                       mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None, None),
                                 P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                                 P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
        return jax.jit(fn)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None),
                             P(None, None)),
                   out_specs=(P(None, None), P(None, None)),
                   check_vma=False)      # post-merge values ARE replicated
    return jax.jit(fn)


# incremented on every block upload — tests assert steady-state sharded
# search performs ZERO per-query device_put of row blocks (ISSUE 6)
PLACE_COUNT = 0


def place_blocks(blocks: np.ndarray, gids: np.ndarray, mesh: Mesh,
                 scales: np.ndarray | None = None):
    """Upload one [S, R, D] block array + its [S, R] gid map (and, for a
    scaled codec, the [S, R] scale table), row blocks resident on their
    owning shard's device."""
    global PLACE_COUNT
    PLACE_COUNT += 1
    b = jax.device_put(jnp.asarray(blocks),
                       NamedSharding(mesh, P(SHARD_AXIS, None, None)))
    g = jax.device_put(jnp.asarray(gids),
                       NamedSharding(mesh, P(SHARD_AXIS, None)))
    if scales is None:
        return b, g
    s = jax.device_put(jnp.asarray(scales),
                       NamedSharding(mesh, P(SHARD_AXIS, None)))
    return b, g, s


@dataclasses.dataclass(frozen=True)
class ExactBlocks:
    """Device-resident exact-phase row blocks, built once per mutation
    epoch and reused for every query until the index mutates (the same
    invalidation contract the serve-layer LRU uses)."""
    mesh: Mesh
    blocks: jax.Array            # [S, R, D] sharded over "shard"
    gids: jax.Array              # [S, R] sharded over "shard"
    n_rows: int                  # total live rows across groups


def build_exact_blocks(groups, dim: int, *, normalize: bool = False
                       ) -> ExactBlocks | None:
    """Host repack + upload of per-shard row groups -> placed blocks.

    groups: list of (vectors [n_s, D], gids [n_s]) — one entry per shard
    (n_s may be 0). Returns None when every group is empty (degenerate
    case: no block array is materialized and nothing touches a device).
    The expensive half of the old one-shot ``fanout_exact_topk``; cache
    the result keyed by ``mutation_epoch`` and query it many times via
    ``exact_topk_blocks``.
    """
    s = len(groups)
    total = sum(v.shape[0] for v, _ in groups)
    if total == 0:
        return None
    r = max(v.shape[0] for v, _ in groups)
    blocks = np.zeros((s, r, dim), np.float32)
    gids = np.full((s, r), -1, np.int32)
    for j, (v, g) in enumerate(groups):
        if v.shape[0]:
            blocks[j, :v.shape[0]] = normalize_rows(v) if normalize else v
            gids[j, :v.shape[0]] = g
    mesh = shard_mesh(s)
    bl, gi = place_blocks(blocks, gids, mesh)
    return ExactBlocks(mesh=mesh, blocks=bl, gids=gi, n_rows=total)


def exact_topk_blocks(placed: ExactBlocks, queries, k: int, *, metric: str,
                      wire_bf16: bool | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Query already-placed exact-phase blocks: zero host-byte movement
    on the steady-state path — one compiled dispatch over resident
    device blocks."""
    q = jnp.asarray(queries, jnp.float32)
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    fn = _fanout_topk_fn(placed.mesh, k, metric,
                         wire_bf16=resolve_wire_bf16(wire_bf16))
    d, g = fn(placed.blocks, placed.gids, q)
    return np.asarray(d), np.asarray(g)


def fanout_exact_topk(groups, queries, k: int, *, metric: str,
                      normalize: bool = False,
                      wire_bf16: bool | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
    """One-shot sharded exact search over explicit per-shard row groups
    (``build_exact_blocks`` + ``exact_topk_blocks`` back to back; callers
    with a mutation epoch should cache the built blocks instead).
    queries [B, D] -> (dists [B, k], gids [B, k]), missing slots
    (INF, -1); all-empty groups short-circuit host-side with no device
    work at all.
    """
    queries = np.asarray(queries, np.float32)
    placed = build_exact_blocks(groups, queries.shape[1],
                                normalize=normalize)
    if placed is None:
        b = queries.shape[0]
        return (np.full((b, k), INF, np.float32),
                np.full((b, k), -1, np.int32))
    return exact_topk_blocks(placed, queries, k, metric=metric,
                             wire_bf16=wire_bf16)


# ---------------------------------------------------------------------------
# the mutable substrate
# ---------------------------------------------------------------------------
class ShardedRows:
    """Keyed mutable row storage partitioned across the mesh.

    The flat and IVF backends delegate their storage, routing, and
    bookkeeping here; HNSW/tiered use the routing + fan-out helpers.
    All mutators are host-side and cheap; device blocks are packed
    lazily on the first search after a mutation (the same laziness the
    single-device backends always had).
    """

    def __init__(self, *, n_shards: int = 1, metric: str = "cosine",
                 dim: int | None = None, normalize_on_pack: bool = False,
                 codec: VectorCodec | str | None = None,
                 wire_bf16: bool | None = None):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.n_shards = n_shards
        self.metric = metric
        self.dim = dim
        # None -> REPRO_WIRE_BF16 env default (resolve_wire_bf16)
        self.wire_bf16 = wire_bf16
        # metric-appropriate normalization at pack time (flat semantics);
        # IVF normalizes at insert instead and packs raw. Under a LOSSY
        # codec the normalization moves to ingest (rows must be in final
        # form BEFORE they are quantized once, DESIGN.md §9) and pack
        # uploads the canonical encoded rows untouched.
        self.normalize_on_pack = normalize_on_pack
        self.codec = (codec if isinstance(codec, VectorCodec)
                      else get_codec(codec or "fp32"))
        # canonical: fp32 decode (insertion-ordered; what reranking,
        # training, and the exact phases read) + for lossy codecs the
        # encoded rows and per-row scales (what devices and snapshots
        # hold — encoded ONCE at ingest, never re-derived)
        self._vecs = np.zeros((0, dim or 0), np.float32)
        self._enc = (np.zeros((0, dim or 0), self.codec.enc_dtype)
                     if self.codec.lossy else None)
        self._scales = (np.zeros(0, np.float32)
                        if self.codec.uses_scales else None)
        self._keys: list[str] = []
        self._key2row: dict[str, int] = {}
        self._alive = np.zeros(0, bool)
        # placement
        self._row_shard = np.zeros(0, np.int32)
        self._row_slot = np.zeros(0, np.int32)
        self._slots: list[list[int]] = [[] for _ in range(n_shards)]
        self._free: list[list[int]] = [[] for _ in range(n_shards)]
        # device (lazy)
        self._device = None          # S>1: (mesh, blocks, gids, scl)
        self._flat = None            # S==1: FlatIndex over live rows
        self._live_rows: np.ndarray | None = None

    # ------------------------------------------------------------ canonical
    @property
    def vectors(self) -> np.ndarray:
        return self._vecs

    @property
    def encoded(self) -> np.ndarray | None:
        """Canonical codec-encoded rows [T, D] (None for fp32)."""
        return self._enc

    @property
    def scales(self) -> np.ndarray | None:
        """Canonical per-row decode scales [T] (int8 codec only)."""
        return self._scales

    @property
    def alive(self) -> np.ndarray:
        return self._alive

    @property
    def exact_distances(self) -> bool:
        """Whether ``topk`` returns the float32 distances over the stored
        rows in their exact order: always on one shard, and on S > 1
        unless the fan-out merges over the bf16 wire."""
        return self.n_shards == 1 or not resolve_wire_bf16(self.wire_bf16)

    @property
    def key_list(self) -> list[str]:
        return self._keys

    @property
    def key2row(self) -> dict[str, int]:
        return self._key2row

    @property
    def size(self) -> int:
        return len(self._key2row)

    @property
    def row_count(self) -> int:
        return len(self._keys)

    def live_keys(self) -> list[str]:
        return [k for i, k in enumerate(self._keys) if self._alive[i]]

    def key_of_row(self, row: int) -> str:
        return self._keys[row]

    def placement_of_row(self, row: int) -> tuple[int, int]:
        """-> (shard, slot) of a live row."""
        return int(self._row_shard[row]), int(self._row_slot[row])

    def shard_stats(self) -> list[dict]:
        """Per-shard occupancy: live rows, free slots, block capacity."""
        out = []
        for s in range(self.n_shards):
            free = len(self._free[s])
            out.append({"shard": s, "slots": len(self._slots[s]),
                        "free": free, "live": len(self._slots[s]) - free})
        return out

    # ------------------------------------------------------------ mutation
    def _invalidate(self) -> None:
        self._device = None
        self._flat = None
        self._live_rows = None

    def _ensure_dim(self, d: int) -> None:
        if self.dim is None:
            self.dim = d
            self._vecs = np.zeros((0, d), np.float32)
            if self._enc is not None:
                self._enc = np.zeros((0, d), self.codec.enc_dtype)

    def _ingest(self, vecs: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray | None, np.ndarray | None]:
        """Raw fp32 rows -> (canonical fp32, encoded, scales).

        Lossy codecs quantize HERE, once, after any metric normalization
        (DESIGN.md §9): the encoded rows become canonical and the fp32
        side is their exact decode, so re-encoding never happens and
        snapshot round-trips are bit-stable. fp32 passes through
        untouched (the historical path)."""
        vecs = np.asarray(vecs, np.float32)
        if not self.codec.lossy:
            return vecs, None, None
        if self.normalize_on_pack and self.metric == "cosine":
            vecs = normalize_rows(vecs)
        enc, scales = self.codec.encode(vecs)
        return self.codec.decode(enc, scales), enc, scales

    def _claim_slot(self, shard: int, row: int) -> int:
        free = self._free[shard]
        if free:
            slot = free.pop()
            self._slots[shard][slot] = row
        else:
            slot = len(self._slots[shard])
            self._slots[shard].append(row)
        return slot

    def _release_row(self, row: int) -> None:
        self._alive[row] = False
        s, slot = int(self._row_shard[row]), int(self._row_slot[row])
        self._slots[s][slot] = -1
        self._free[s].append(slot)

    def _append_enc(self, enc: np.ndarray | None,
                    scales: np.ndarray | None) -> None:
        if self._enc is not None:
            self._enc = np.concatenate([self._enc, enc])
        if self._scales is not None:
            self._scales = np.concatenate(
                [self._scales, np.asarray(scales, np.float32)])

    def _append_row(self, key: str, vec: np.ndarray) -> int:
        row = len(self._keys)
        self._vecs = np.concatenate([self._vecs, vec[None]])
        self._keys.append(key)
        self._alive = np.concatenate([self._alive, np.ones(1, bool)])
        self._key2row[key] = row
        shard = shard_of_key(key, self.n_shards)
        slot = self._claim_slot(shard, row)
        self._row_shard = np.concatenate(
            [self._row_shard, np.array([shard], np.int32)])
        self._row_slot = np.concatenate(
            [self._row_slot, np.array([slot], np.int32)])
        return row

    def upsert(self, key: str, vec: np.ndarray) -> None:
        vec = np.asarray(vec, np.float32).reshape(-1)
        self._ensure_dim(vec.shape[0])
        vec, enc, scales = self._ingest(vec[None])
        old = self._key2row.pop(key, None)
        if old is not None:
            self._release_row(old)
        self._append_row(key, vec[0])
        self._append_enc(enc, scales)
        self._invalidate()

    def upsert_many(self, keys: list[str], vecs: np.ndarray) -> None:
        vecs = np.asarray(vecs, np.float32)
        self._ensure_dim(vecs.shape[1])
        vecs, enc, scales = self._ingest(vecs)
        # pop as we release: a pre-existing key repeated WITHIN the batch
        # must free its old slot exactly once (a double release would
        # push the slot onto the free stack twice and hand it to two rows)
        for key in keys:
            old = self._key2row.pop(key, None)
            if old is not None:
                self._release_row(old)
        base = len(self._keys)
        n = len(keys)
        self._vecs = np.concatenate([self._vecs, vecs])
        self._append_enc(enc, scales)
        self._keys.extend(keys)
        self._alive = np.concatenate([self._alive, np.ones(n, bool)])
        shards = np.zeros(n, np.int32)
        slots = np.zeros(n, np.int32)
        for j, key in enumerate(keys):
            self._key2row[key] = base + j
            shards[j] = shard_of_key(key, self.n_shards)
            slots[j] = self._claim_slot(int(shards[j]), base + j)
        self._row_shard = np.concatenate([self._row_shard, shards])
        self._row_slot = np.concatenate([self._row_slot, slots])
        self._invalidate()

    def tombstone(self, key: str) -> None:
        self._release_row(self._key2row.pop(key))
        self._invalidate()

    def contains(self, key: str) -> bool:
        return key in self._key2row

    def compact(self) -> None:
        """Physically drop tombstoned rows: canonical arrays re-pack over
        live rows and the per-shard slot tables are rebuilt dense — the
        complement of the store layer's secure-delete page rewrite
        (DESIGN.md §7): after this, a deleted vector's bytes — the fp32
        decode AND the codec-encoded bytes + scale (DESIGN.md §9) —
        exist in no host array and in no shard's device block."""
        live = np.flatnonzero(self._alive)
        vecs = np.ascontiguousarray(self._vecs[live])
        keys = [self._keys[i] for i in live]
        enc = (np.ascontiguousarray(self._enc[live])
               if self._enc is not None else None)
        scales = (np.ascontiguousarray(self._scales[live])
                  if self._scales is not None else None)
        self._reset_layout(vecs, keys, np.ones(live.size, bool),
                           enc=enc, scales=scales)

    def _reset_layout(self, vecs: np.ndarray, keys: list[str],
                      alive: np.ndarray, enc: np.ndarray | None = None,
                      scales: np.ndarray | None = None) -> None:
        """Adopt canonical arrays and re-derive placement from scratch
        (compaction, restore, resharding all land here)."""
        self._vecs = np.asarray(vecs, np.float32)
        if self._enc is not None:
            if enc is None:
                raise ValueError(
                    f"{self.codec.name} rows need their encoded arrays; "
                    "got fp32-only state (cross-dtype restore?)")
            self._enc = np.asarray(enc, self.codec.enc_dtype)
        if self._scales is not None:
            self._scales = np.asarray(scales, np.float32)
        if self._vecs.shape[1]:
            self.dim = int(self._vecs.shape[1])
        self._keys = list(keys)
        self._alive = np.asarray(alive, bool).copy()
        self._key2row = {k: i for i, k in enumerate(self._keys)
                         if self._alive[i]}
        n = len(self._keys)
        self._row_shard = np.full(n, -1, np.int32)
        self._row_slot = np.full(n, -1, np.int32)
        self._slots = [[] for _ in range(self.n_shards)]
        self._free = [[] for _ in range(self.n_shards)]
        for row in range(n):
            if not self._alive[row]:
                continue                 # dead rows own no slot
            shard = shard_of_key(self._keys[row], self.n_shards)
            self._row_shard[row] = shard
            self._row_slot[row] = self._claim_slot(shard, row)
        self._invalidate()

    def restore(self, vecs: np.ndarray, keys: list[str],
                alive: np.ndarray) -> None:
        """Inverse of the canonical accessors: placement is re-derived,
        which is why a snapshot reshards freely (DESIGN.md §8)."""
        if self.codec.lossy:
            raise ValueError(
                f"{self.codec.name} rows restore from encoded state "
                "(restore_encoded); got fp32-only state — the store was "
                "written by a different storage dtype")
        self._reset_layout(vecs, keys, alive)

    def restore_encoded(self, enc: np.ndarray, scales: np.ndarray | None,
                        keys: list[str], alive: np.ndarray) -> None:
        """Adopt snapshotted encoded rows (+ scales) as canonical and
        re-derive the fp32 side by decoding — the encoded array is never
        re-derived, so restore is bit-for-bit (DESIGN.md §9)."""
        enc = self.codec.from_storage(enc)
        self._reset_layout(self.codec.decode(enc, scales), keys, alive,
                           enc=enc, scales=scales)

    # --------------------------------------------------------------- pack
    def _maybe_relayout(self) -> None:
        total = sum(len(s) for s in self._slots)
        free = sum(len(f) for f in self._free)
        if total and free / total > REPACK_FREE_FRACTION:
            # too many dead slots: re-derive a dense layout (slot churn
            # is fine here — the device blocks are being rebuilt anyway)
            self._reset_layout(self._vecs, self._keys, self._alive)

    def pack(self):
        """(Re)build the device placement over live rows.

        S == 1 -> a ``FlatIndex`` (bit-for-bit the pre-shard path for
                  fp32; encoded rows + scale column for lossy codecs).
        S > 1  -> (mesh, blocks [S,R,D], gids [S,R], scales [S,R]|None).
                  Blocks hold the codec-encoded rows, so device
                  bytes shrink with the codec (DESIGN.md §9).
        """
        live = np.flatnonzero(self._alive)
        if live.size == 0:
            raise ValueError("index is empty")
        lossy = self.codec.lossy
        if self.n_shards == 1:
            if self._flat is None:
                from repro.core.flat import FlatIndex
                self._live_rows = live
                if lossy:
                    # rows were normalized + encoded at ingest; upload
                    # the canonical encoded bytes as-is
                    self._flat = FlatIndex(
                        vectors=jnp.asarray(self._enc[live]),
                        metric=self.metric,
                        scales=(jnp.asarray(self._scales[live])
                                if self._scales is not None else None))
                else:
                    v = self._vecs[live]
                    self._flat = (FlatIndex.build(v, metric=self.metric)
                                  if self.normalize_on_pack else
                                  FlatIndex(vectors=jnp.asarray(v),
                                            metric=self.metric))
            return self._flat
        if self._device is None:
            self._maybe_relayout()
            mesh = shard_mesh(self.n_shards)
            r = max(max(len(s) for s in self._slots), 1)
            rows_src = self._enc if lossy else self._vecs
            blocks = np.zeros((self.n_shards, r, self.dim or 1),
                              rows_src.dtype)
            gids = np.full((self.n_shards, r), -1, np.int32)
            scl = (np.zeros((self.n_shards, r), np.float32)
                   if self._scales is not None else None)
            for s in range(self.n_shards):
                table = np.asarray(self._slots[s], np.int64)
                occ = np.flatnonzero(table >= 0)     # occupied slots only
                if occ.size:
                    blocks[s, occ] = rows_src[table[occ]]
                    gids[s, occ] = table[occ]
                    if scl is not None:
                        scl[s, occ] = self._scales[table[occ]]
            if not lossy and self.normalize_on_pack \
                    and self.metric == "cosine":
                # row-wise, so identical bits to normalizing each shard's
                # rows separately; free slots stay zero (norm clamped)
                blocks = normalize_rows(blocks)
            if scl is None:
                bl, gi = place_blocks(blocks, gids, mesh)
                sc = None
            else:
                bl, gi, sc = place_blocks(blocks, gids, mesh, scl)
            self._device = (mesh, bl, gi, sc)
        return self._device

    # -------------------------------------------------------------- search
    def topk(self, queries: np.ndarray, k: int
             ) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-k over live rows (asymmetric under a lossy codec:
        fp32 query vs encoded rows) -> (dists, global row ids).

        S == 1 returns ``min(k, live)`` columns (exactly the historical
        single-device behaviour — callers pad); S > 1 always returns k
        columns with missing slots as (INF, -1).
        """
        q = np.asarray(queries, np.float32)
        rows = q.shape[0]
        if self.n_shards == 1:
            with span("index.scan", rows=rows):
                flat = self.pack()
                d, i = flat.query(q, min(k, flat.n))
            with span("index.fetch", rows=rows):
                d, i = np.asarray(d), np.asarray(i)
                return d, self._live_rows[i]
        with span("index.scan", rows=rows):
            mesh, blocks, gids, scl = self.pack()
            qj = jnp.asarray(q)
            if self.metric == "cosine" and self.normalize_on_pack:
                qj = qj / jnp.maximum(
                    jnp.linalg.norm(qj, axis=-1, keepdims=True), 1e-12)
            wire = resolve_wire_bf16(self.wire_bf16)
            fn = _fanout_topk_fn(mesh, k, self.metric,
                                 has_scales=scl is not None, wire_bf16=wire)
            d, g = (fn(blocks, gids, scl, qj) if scl is not None
                    else fn(blocks, gids, qj))
        with span("index.fetch", rows=rows):
            return np.asarray(d), np.asarray(g)

    def rerank_topk(self, queries: np.ndarray, gids: np.ndarray, k: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Exact fp32 re-scoring of over-fetched candidates against the
        canonical host rows (DESIGN.md §9): the second half of the lossy
        search contract (asymmetric first pass over-fetches
        ``k·rerank_factor``, this picks the true best k)."""
        return rerank_exact(self._vecs, queries, gids, k,
                            metric=self.metric)

    def device_block_bytes(self) -> int:
        """Bytes the packed device representation holds per the current
        live set (blocks + gid map + scale table) — the codec's device
        footprint (benchmarks/bench_memory.py)."""
        packed = self.pack()
        if self.n_shards == 1:
            total = packed.vectors.nbytes
            if packed.scales is not None:
                total += packed.scales.nbytes
            return total
        _, bl, gi, sc = packed
        return bl.nbytes + gi.nbytes + (sc.nbytes if sc is not None else 0)
