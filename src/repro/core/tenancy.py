"""Multi-tenant index pool: many small private indexes, one device arena
(DESIGN.md §10).

MeMemo's deployment shape is millions of *per-user* corpora, not one big
index — a user's few-hundred-row private knowledge base is the unit of
isolation, admission, and deletion. Before this layer the process served
exactly one ``VectorIndex``; naively instantiating one index per tenant
would cost one XLA buffer (and one compiled search) per user.

``IndexPool`` multiplexes tenants over ONE shared ``ShardedRows`` arena:

  * **namespacing** — a tenant's rows live in the arena under
    ``tenant_id + NS_SEP + key``; the same blake2b key->shard routing
    spreads every tenant across the mesh.
  * **slab allocation** — ``SlabRows`` hands out per-shard slot capacity
    in fixed ``slab_rows``-sized slabs, each owned by exactly one tenant
    at a time. Resident tenants therefore pack into shared ``[S, R, D]``
    device blocks (one buffer for the whole pool, DESIGN.md §8) while a
    tenant's *search* gathers only its own rows — per-query cost scales
    with the tenant's corpus, not the arena.
  * **per-tenant epochs** — the pool keeps a ``mutation_epoch`` per
    tenant with exactly the per-op bump schedule a dedicated
    ``FlatVectorIndex`` would have, so one user's delete invalidates
    only *their* cache entries (serve/retrieval.py keys its LRU on
    ``(tenant, query, ...)`` and validates per tenant).
  * **LRU residency** — at most ``max_resident`` tenants hold arena
    capacity; the rest live in per-tenant ``IndexStore`` dirs
    (``root/tenants/<id>``, DESIGN.md §7). Evict = snapshot + remove the
    tenant's rows from the arena + ``_drop_derived()``; admit = the
    existing bit-for-bit warm restore adopted back into the arena.
    Because the stored state is the same canonical (codec-encoded)
    arrays a single index persists, evict→restore round-trips are
    bit-identical to a never-evicted index.
  * **byte absence, per tenant** — ``compact(tid)`` physically removes
    the tenant's tombstoned rows from the host arrays, from the shared
    device blocks (rebuilt without them), and from the tenant's store
    (snapshot + WAL truncation + old-snapshot purge — the secure-delete
    contract of DESIGN.md §7, scoped to one tenant). Other tenants'
    rows, epochs, and cached results are untouched.

What shared slabs do NOT guarantee before compaction: a tombstoned row's
bytes remain in the tenant's own host-canonical arrays (and store WAL)
until ``compact(tid)`` — exactly like a single index. They are, however,
never packed into device blocks again, never returned by any query, and
never visible to another tenant: a freed slab handed to tenant B is
zero-filled at pack time (free slots carry gid -1 and 0-rows), so slab
reuse cannot expose the previous owner's vectors.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import urllib.parse

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.codec import VectorCodec, get_codec
from repro.core.flat import FlatVectorIndex, _pad_results
from repro.core.hnsw_build import normalize_rows
from repro.core.sharded import (INF, SHARD_AXIS, ShardedRows, place_blocks,
                                shard_mesh, shard_of_key, trim_merge_width)
from repro.distributed.collectives import hierarchical_topk
from repro.kernels import ops

# Unit separator: cannot appear in tenant ids or doc keys (validated at
# the pool boundary), so the namespaced key is unambiguous.
NS_SEP = "\x1f"


def tenant_key(tid: str, key: str) -> str:
    """Namespaced arena key for one tenant's document."""
    return tid + NS_SEP + key


def split_tenant_key(nskey: str) -> tuple[str, str]:
    """Inverse of :func:`tenant_key` -> (tenant_id, doc key)."""
    tid, _, key = nskey.partition(NS_SEP)
    return tid, key


# ---------------------------------------------------------------------------
# compiled tenant-scoped search (slab gather + fused top-k + tree merge)
# ---------------------------------------------------------------------------
def _slab_gather(blocks, gids, scl, rows):
    """Gather one tenant's live rows out of a shard's packed block.

    blocks [RT, D] (RT = n_slabs * slab_rows), gids [RT], rows [M] slot
    positions in arena row order (-1 padding) -> (db [M, D], gid [M],
    scales [M] | None). Padding entries clip to slot 0 — which may hold
    ANOTHER tenant's live row — so their gathered gids are force-masked
    to -1 here; nothing downstream may trust a gid at a padded position.
    """
    idx = jnp.clip(rows, 0, blocks.shape[0] - 1)
    db = jnp.take(blocks, idx, axis=0)
    g = jnp.where(rows >= 0, jnp.take(gids, idx), -1)
    s = None if scl is None else jnp.take(scl, idx)
    return db, g, s


def _slab_local_topk(blocks, gids, scl, tbl, q, *, k: int, metric: str):
    """One shard's tenant-scoped top-k: gather the tenant's live rows in
    arena row order, run the SAME fused ``flat_topk`` kernel the
    single-index path uses over the [M, D] gathered db with the padding
    rows masked in the scan, and trim to the k-wide merge format. The
    scan breaks ties on position, so equal distances come back in the
    tenant's own row order, as on a dedicated index."""
    db, g, s = _slab_gather(blocks, gids, scl, tbl)
    d, i = ops.flat_topk(db, q, min(k, db.shape[0]), metric=metric,
                         scales=s, valid=g >= 0)
    gg = jnp.take(g, i)
    d = jnp.where(gg >= 0, d, jnp.float32(INF))
    d, gg = trim_merge_width(d, gg, k, jnp.float32(INF))
    gg = jnp.where(d >= jnp.float32(INF), -1, gg)
    return d, gg


@functools.lru_cache(maxsize=256)
def _slab_topk_single(k: int, metric: str, has_scales: bool):
    """S == 1 tenant search: one fused dispatch over the gathered slabs."""
    def run(blocks, gids, scl, tbl, q):
        if metric == "cosine":
            q = q / jnp.maximum(
                jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        return _slab_local_topk(blocks, gids, scl, tbl, q, k=k,
                                metric=metric)

    if has_scales:
        return jax.jit(run)
    return jax.jit(lambda blocks, gids, tbl, q: run(blocks, gids, None,
                                                    tbl, q))


@functools.lru_cache(maxsize=256)
def _slab_topk_sharded(mesh, k: int, metric: str, has_scales: bool):
    """S > 1 tenant search: per-shard row gather + fused scan under
    shard_map, merged through the same ppermute tree as the single-index
    fan-out (ids exact, ties break on the smaller gid)."""
    n_shards = mesh.shape[SHARD_AXIS]

    def local(blocks, gids, scl, tbl, q):
        blocks, gids, tbl = blocks[0], gids[0], tbl[0]
        scl = None if scl is None else scl[0]
        if metric == "cosine":
            q = q / jnp.maximum(
                jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        d, gg = _slab_local_topk(blocks, gids, scl, tbl, q, k=k,
                                 metric=metric)
        return hierarchical_topk(d, gg, k, (SHARD_AXIS,), tie_break_ids=True,
                                 axis_sizes=(n_shards,))

    if has_scales:
        fn = shard_map(local, mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None, None),
                                 P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                                 P(SHARD_AXIS, None), P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    else:
        fn = shard_map(lambda b, g, t, q: local(b, g, None, t, q), mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None, None),
                                 P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                                 P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return jax.jit(fn)


def _multi_local_topk(blocks, gids, scl, tbl, q, *, k: int, metric: str):
    """Cross-tenant one-dispatch search: every query row carries its OWN
    row table. tbl [B, M], q [B, D] -> (d [B, k], gids [B, k]).

    Per-query gather ([B, M, D]) + masked einsum + top_k: unlike the
    single-tenant path this one is plain jnp, not the fused kernel.
    Rows are decoded in-graph (bf16 upcast / int8 * scale) — the same
    asymmetric-scan semantics as ``flat_topk``'s fused decode.
    """
    idx = jnp.clip(tbl, 0, blocks.shape[0] - 1)             # [B, M]
    x = jnp.take(blocks, idx, axis=0).astype(jnp.float32)   # [B, M, D]
    g = jnp.where(tbl >= 0, jnp.take(gids, idx), -1)        # [B, M]
    if scl is not None:
        x = x * jnp.take(scl, idx)[..., None]
    if metric == "cosine":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True),
                            1e-12)
    if metric == "l2":
        d = (jnp.sum(q * q, axis=-1)[:, None]
             - 2.0 * jnp.einsum("bmd,bd->bm", x, q)
             + jnp.sum(x * x, axis=-1))
    else:
        d = jnp.float32(1.0) - jnp.einsum("bmd,bd->bm", x, q)
    d = jnp.where(g >= 0, d, jnp.float32(INF))
    kk = min(k, d.shape[1])
    neg, j = jax.lax.top_k(-d, kk)
    dd = -neg
    gg = jnp.take_along_axis(g, j, axis=1)
    dd, gg = trim_merge_width(dd, gg, k, jnp.float32(INF))
    gg = jnp.where(dd >= jnp.float32(INF), -1, gg)
    return dd, gg


@functools.lru_cache(maxsize=256)
def _slab_topk_multi(mesh, k: int, metric: str, has_scales: bool):
    """Compiled cross-tenant dispatch; ``mesh`` is None for S == 1."""
    if mesh is None:
        def run(blocks, gids, scl, tbl, q):
            return _multi_local_topk(blocks, gids, scl, tbl, q, k=k,
                                     metric=metric)
        if has_scales:
            return jax.jit(run)
        return jax.jit(lambda blocks, gids, tbl, q: run(blocks, gids, None,
                                                        tbl, q))
    n_shards = mesh.shape[SHARD_AXIS]

    def local(blocks, gids, scl, tbl, q):
        blocks, gids, tbl = blocks[0], gids[0], tbl[0]
        scl = None if scl is None else scl[0]
        d, gg = _multi_local_topk(blocks, gids, scl, tbl, q, k=k,
                                  metric=metric)
        return hierarchical_topk(d, gg, k, (SHARD_AXIS,), tie_break_ids=True,
                                 axis_sizes=(n_shards,))

    if has_scales:
        fn = shard_map(local, mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None, None),
                                 P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                                 P(SHARD_AXIS, None, None), P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    else:
        fn = shard_map(lambda b, g, t, q: local(b, g, None, t, q), mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None, None),
                                 P(SHARD_AXIS, None),
                                 P(SHARD_AXIS, None, None), P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# slab-granular arena
# ---------------------------------------------------------------------------
class SlabRows(ShardedRows):
    """``ShardedRows`` whose per-shard slot space is carved into fixed
    ``slab_rows``-sized slabs, each owned by one tenant at a time.

    The canonical layer (host vectors / keys / alive) is untouched —
    rows append in arena order exactly as before, so per-tenant
    extraction preserves each tenant's own insertion order (what the
    store-parity contract needs). Only *placement* changes: a row's slot
    comes from a slab owned by its tenant (``_owner_of_row`` parses the
    namespace prefix), a tombstoned slot returns to its slab, and a slab
    whose slots are all free is released to the arena-wide pool for the
    next tenant that needs capacity. ``pack_arena`` zero-fills free
    slots, so a reused slab never carries its previous owner's bytes to
    the device.
    """

    def __init__(self, *, slab_rows: int = 64, n_shards: int = 1,
                 metric: str = "cosine", dim: int | None = None,
                 codec: VectorCodec | str | None = None):
        if slab_rows < 1:
            raise ValueError(f"slab_rows must be >= 1, got {slab_rows}")
        self.slab_rows = int(slab_rows)
        # per shard: slab -> owner tenant (None = free), slab -> free-slot
        # stack, owner -> slab ids (insertion order = allocation order)
        self._slab_owner: list[list[str | None]] = \
            [[] for _ in range(n_shards)]
        self._slab_free: list[list[list[int]]] = \
            [[] for _ in range(n_shards)]
        self._owner_slabs: list[dict[str, list[int]]] = \
            [{} for _ in range(n_shards)]
        # derived-state versioning: bumped on every _invalidate so the
        # lazily-built device arena and per-tenant slab tables self-stale
        self.pack_epoch = 0
        self._arena = None
        self._tables: dict[str, tuple] = {}
        super().__init__(n_shards=n_shards, metric=metric, dim=dim,
                         normalize_on_pack=True, codec=codec)

    # --------------------------------------------------------- slab layout
    def _owner_of_row(self, row: int) -> str:
        return self._keys[row].partition(NS_SEP)[0]

    def _alloc_slab(self, shard: int, owner: str) -> int:
        """Hand ``owner`` a slab on ``shard``: reuse a released slab if
        one exists (its slots are already free + zero-packed), else grow
        the shard's slot space by one slab."""
        owners = self._slab_owner[shard]
        r = self.slab_rows
        j = next((i for i, o in enumerate(owners) if o is None), None)
        if j is None:
            j = len(owners)
            owners.append(owner)
            self._slab_free[shard].append([])
            base = j * r
            self._slots[shard].extend([-1] * r)
            self._free[shard].extend(range(base, base + r))
        else:
            owners[j] = owner
        # canonical allocation order inside the slab (deterministic
        # regardless of the previous owner's release order)
        self._slab_free[shard][j] = list(range((j + 1) * r - 1,
                                               j * r - 1, -1))
        self._owner_slabs[shard].setdefault(owner, []).append(j)
        return j

    def _free_slab(self, shard: int, j: int) -> None:
        owner = self._slab_owner[shard][j]
        self._slab_owner[shard][j] = None
        slabs = self._owner_slabs[shard].get(owner)
        if slabs is not None:
            slabs.remove(j)
            if not slabs:
                del self._owner_slabs[shard][owner]

    def _take_slot(self, shard: int, j: int, row: int) -> int:
        slot = self._slab_free[shard][j].pop()
        self._slots[shard][slot] = row
        self._free[shard].remove(slot)
        return slot

    def _claim_slot(self, shard: int, row: int) -> int:
        owner = self._owner_of_row(row)
        for j in self._owner_slabs[shard].get(owner, ()):
            if self._slab_free[shard][j]:
                return self._take_slot(shard, j, row)
        return self._take_slot(shard, self._alloc_slab(shard, owner), row)

    def _release_row(self, row: int) -> None:
        shard, slot = int(self._row_shard[row]), int(self._row_slot[row])
        super()._release_row(row)
        j = slot // self.slab_rows
        self._slab_free[shard][j].append(slot)
        if len(self._slab_free[shard][j]) == self.slab_rows:
            self._free_slab(shard, j)      # wholly empty -> reusable

    def _reset_layout(self, vecs, keys, alive, enc=None, scales=None) -> None:
        self._slab_owner = [[] for _ in range(self.n_shards)]
        self._slab_free = [[] for _ in range(self.n_shards)]
        self._owner_slabs = [{} for _ in range(self.n_shards)]
        super()._reset_layout(vecs, keys, alive, enc=enc, scales=scales)

    def _maybe_relayout(self) -> None:
        # slab padding is by-design free capacity, not dead weight: the
        # base free-fraction repack would thrash the slab assignment on
        # every pack. Dead slots are reclaimed per tenant by compact()
        # and evict() instead.
        pass

    def _invalidate(self) -> None:
        super()._invalidate()
        self._arena = None
        self._tables.clear()
        self.pack_epoch += 1

    # ---------------------------------------------------- tenant extraction
    def owner_mask(self, tid: str) -> np.ndarray:
        """Bool [T] mask of arena rows (live AND tombstoned) owned by
        ``tid``."""
        pre = tid + NS_SEP
        n = len(self._keys)
        return np.fromiter((k.startswith(pre) for k in self._keys),
                           bool, count=n) if n else np.zeros(0, bool)

    def tenant_rows(self, tid: str):
        """Extract one tenant's canonical state, in the tenant's own
        insertion order, with raw (un-namespaced) keys ->
        (keys, vecs, alive, enc, scales). Includes tombstoned rows: this
        is exactly the state a dedicated single index would persist."""
        idx = np.flatnonzero(self.owner_mask(tid))
        keys = [self._keys[i].partition(NS_SEP)[2] for i in idx]
        d = self.dim or 0
        vecs = (np.ascontiguousarray(self._vecs[idx]) if idx.size
                else np.zeros((0, d), np.float32))
        alive = self._alive[idx].copy() if idx.size else np.zeros(0, bool)
        enc = scales = None
        if self._enc is not None:
            enc = (np.ascontiguousarray(self._enc[idx]) if idx.size
                   else np.zeros((0, d), self.codec.enc_dtype))
        if self._scales is not None:
            scales = (np.ascontiguousarray(self._scales[idx]) if idx.size
                      else np.zeros(0, np.float32))
        return keys, vecs, alive, enc, scales

    def adopt_rows(self, keys: list[str], vecs: np.ndarray,
                   alive: np.ndarray, enc: np.ndarray | None = None,
                   scales: np.ndarray | None = None) -> None:
        """Append restored tenant rows (namespaced keys) preserving the
        canonical encodings — the arena-side half of warm restore. Rows
        arrive in the tenant's stored order; dead rows keep their
        tombstone and own no slot (same as ``_reset_layout``)."""
        vecs = np.asarray(vecs, np.float32)
        alive = np.asarray(alive, bool)
        n = len(keys)
        if n and vecs.shape[1]:
            self._ensure_dim(int(vecs.shape[1]))
        self._vecs = np.concatenate([self._vecs, vecs])
        if self._enc is not None:
            if enc is None:
                raise ValueError(
                    f"{self.codec.name} arena needs encoded rows to adopt")
            self._enc = np.concatenate(
                [self._enc, np.asarray(enc, self.codec.enc_dtype)])
        if self._scales is not None:
            self._scales = np.concatenate(
                [self._scales, np.asarray(scales, np.float32)])
        base = len(self._keys)
        self._keys.extend(keys)
        self._alive = np.concatenate([self._alive, alive])
        shards = np.full(n, -1, np.int32)
        slots = np.full(n, -1, np.int32)
        for j, key in enumerate(keys):
            if not alive[j]:
                continue
            row = base + j
            self._key2row[key] = row
            s = shard_of_key(key, self.n_shards)
            shards[j] = s
            slots[j] = self._claim_slot(s, row)
        self._row_shard = np.concatenate([self._row_shard, shards])
        self._row_slot = np.concatenate([self._row_slot, slots])
        self._invalidate()

    def remove_rows(self, keep: np.ndarray) -> None:
        """Physically drop every row where ``keep`` is False: canonical
        arrays re-pack over the kept rows (fresh buffers — the dropped
        vectors' bytes survive in NO host array) and slab placement is
        re-derived. Eviction and per-tenant compaction both land here."""
        keep = np.asarray(keep, bool)
        vecs = np.ascontiguousarray(self._vecs[keep])
        keys = [k for k, m in zip(self._keys, keep) if m]
        alive = self._alive[keep].copy()
        enc = (np.ascontiguousarray(self._enc[keep])
               if self._enc is not None else None)
        scales = (np.ascontiguousarray(self._scales[keep])
                  if self._scales is not None else None)
        self._reset_layout(vecs, keys, alive, enc=enc, scales=scales)

    # ------------------------------------------------------------- device
    def pack_arena(self):
        """(Re)build the SHARED device blocks over every resident
        tenant's live rows: [S, n_slabs*R, D] (+ [S, RT] gids, + scale
        table for int8), uploaded once per mutation epoch. Free slots —
        including every slot of a released slab — are zero-filled with
        gid -1, which is what makes slab reuse safe. S == 1 keeps plain
        single-device arrays (no mesh)."""
        if self._arena is not None:
            return self._arena
        s_n, r = self.n_shards, self.slab_rows
        nsl = max(max((len(o) for o in self._slab_owner), default=0), 1)
        d = self.dim or 1
        lossy = self.codec.lossy
        rows_src = self._enc if lossy else self._vecs
        blocks = np.zeros((s_n, nsl * r, d), rows_src.dtype)
        gids = np.full((s_n, nsl * r), -1, np.int32)
        scl = (np.zeros((s_n, nsl * r), np.float32)
               if self._scales is not None else None)
        for s in range(s_n):
            table = np.asarray(self._slots[s], np.int64)
            occ = np.flatnonzero(table >= 0)
            if occ.size:
                blocks[s, occ] = rows_src[table[occ]]
                gids[s, occ] = table[occ]
                if scl is not None:
                    scl[s, occ] = self._scales[table[occ]]
        if not lossy and self.normalize_on_pack and self.metric == "cosine":
            blocks = normalize_rows(blocks)
        if s_n == 1:
            self._arena = (None, jnp.asarray(blocks[0]),
                           jnp.asarray(gids[0]),
                           None if scl is None else jnp.asarray(scl[0]))
        else:
            mesh = shard_mesh(s_n)
            if scl is None:
                bl, gi = place_blocks(blocks, gids, mesh)
                sc = None
            else:
                bl, gi, sc = place_blocks(blocks, gids, mesh, scl)
            self._arena = (mesh, bl, gi, sc)
        return self._arena

    def arena_device_bytes(self) -> int:
        """Device bytes of the packed shared arena (blocks + gids +
        scales) — the whole pool's footprint, NOT per tenant."""
        _, bl, gi, sc = self.pack_arena()
        return bl.nbytes + gi.nbytes + (sc.nbytes if sc is not None else 0)

    # ------------------------------------------------------------- search
    def tenant_table(self, tid: str):
        """-> (tbl [S, L*R] int32 slot positions (-1 pad), L, live rows).
        Each shard's row lists the tenant's live slots in arena row
        order, which is the tenant's own insertion order: a reused slot
        does not move its new row ahead of older ones, so ties break as
        on a dedicated index. L is the tenant's per-shard slab count
        rounded up to a power of two, so the compiled search is shared
        across tenants of similar size (the batch-bucket trick,
        DESIGN.md §6). Cached per ``pack_epoch``."""
        ent = self._tables.get(tid)
        if ent is not None and ent[0] == self.pack_epoch:
            return ent[1:]
        s_n, r = self.n_shards, self.slab_rows
        per = [self._owner_slabs[s].get(tid, []) for s in range(s_n)]
        mx = max(len(p) for p in per)
        l_pad = 1 if mx <= 1 else 1 << (mx - 1).bit_length()
        tbl = np.full((s_n, l_pad * r), -1, np.int32)
        live = 0
        for s in range(s_n):
            slots = (np.asarray(per[s], np.int64)[:, None] * r
                     + np.arange(r)).ravel()
            rows = np.asarray(self._slots[s], np.int64)[slots]
            occ = slots[rows >= 0][np.argsort(rows[rows >= 0])]
            tbl[s, :occ.size] = occ
            live += occ.size
        out = (tbl, l_pad, live)
        self._tables[tid] = (self.pack_epoch,) + out
        return out

    def tenant_live(self, tid: str) -> int:
        return self.tenant_table(tid)[2]

    def tenant_topk(self, tid: str, queries: np.ndarray, k: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Exact top-k over ONE tenant's live rows -> (dists [B, k],
        arena gids [B, k], (INF, -1)-padded). One compiled dispatch; the
        db it scans is the tenant's rows gathered in-graph, so cost
        scales with the tenant, not the arena."""
        tbl, _, live = self.tenant_table(tid)
        if live == 0:
            raise ValueError("index is empty")
        q = jnp.asarray(np.asarray(queries, np.float32))
        mesh, blocks, gids, scl = self.pack_arena()
        if mesh is None:
            fn = _slab_topk_single(k, self.metric, scl is not None)
            args = (blocks, gids) + (() if scl is None else (scl,)) \
                + (jnp.asarray(tbl[0]), q)
        else:
            fn = _slab_topk_sharded(mesh, k, self.metric, scl is not None)
            args = (blocks, gids) + (() if scl is None else (scl,)) \
                + (jnp.asarray(tbl), q)
        d, g = fn(*args)
        return np.asarray(d), np.asarray(g)

    def multi_topk(self, tables: np.ndarray, queries: np.ndarray, k: int
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Cross-tenant one-dispatch top-k: ``tables`` [S, B, L*R]
        carries one row table per query row (rows of DIFFERENT tenants
        batch together when their padded L matches)."""
        q = jnp.asarray(np.asarray(queries, np.float32))
        mesh, blocks, gids, scl = self.pack_arena()
        fn = _slab_topk_multi(mesh, k, self.metric, scl is not None)
        tb = jnp.asarray(tables[0] if mesh is None else tables)
        args = (blocks, gids) + (() if scl is None else (scl,)) + (tb, q)
        d, g = fn(*args)
        return np.asarray(d), np.asarray(g)


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _TenantState:
    epoch: int = 0
    resident: bool = False
    store: object | None = None        # IndexStore | None
    spill: tuple | None = None         # (arrays, meta) when root is None
    since_snapshot: int = 0


class IndexPool:
    """Tenant-aware multiplexer over one shared :class:`SlabRows` arena.

    Public surface mirrors ``VectorIndex`` with a leading ``tenant_id``
    (mutators validate and raise exactly like a dedicated index, and the
    per-tenant ``epoch(tid)`` follows the same bump schedule), plus the
    pool-only verbs: ``evict``/``admit`` (LRU paging against per-tenant
    ``IndexStore`` dirs), ``compact(tid)`` (per-tenant secure delete),
    and ``query_batch_multi`` (one dispatch across tenants).

    root=None keeps evicted tenants in host memory (tests / ephemeral
    pools); with a root, evicted state lives ONLY on disk.
    """

    def __init__(self, root: str | None = None, *, dim: int | None = None,
                 metric: str = "cosine", n_shards: int = 1,
                 dtype: str = "fp32", rerank_factor: int | None = None,
                 max_resident: int = 64, slab_rows: int = 64,
                 snapshot_every: int | None = None):
        if metric not in ("cosine", "ip", "l2"):
            raise ValueError(f"unknown metric {metric!r}")
        if max_resident < 1:
            raise ValueError(f"max_resident must be >= 1, got {max_resident}")
        self.root = str(root) if root is not None else None
        self.metric = metric
        self.dim = dim
        self.n_shards = int(n_shards)
        self.dtype = str(dtype)
        self.rerank_factor = rerank_factor
        self.max_resident = int(max_resident)
        self.slab_rows = int(slab_rows)
        self.snapshot_every = snapshot_every
        self._codec = get_codec(self.dtype)
        self._arena = SlabRows(slab_rows=self.slab_rows,
                               n_shards=self.n_shards, metric=metric,
                               dim=dim, codec=self._codec)
        self._tenants: dict[str, _TenantState] = {}
        self._resident: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        self._epoch = 0                       # pool-global (engine compat)
        self.stats = {"admissions": 0, "evictions": 0, "snapshots": 0}

    # ----------------------------------------------------------- identity
    @property
    def mutation_epoch(self) -> int:
        """Pool-global mutation counter (sum of all tenants' mutations) —
        the coarse signal non-tenant-aware consumers key on. Tenant-aware
        caches use :meth:`epoch` instead."""
        return self._epoch

    @property
    def shard_count(self) -> int:
        return self.n_shards

    @property
    def storage_dtype(self) -> str:
        return self.dtype

    def epoch(self, tid: str) -> int:
        """Per-tenant mutation epoch — same bump schedule as a dedicated
        index (+1 per insert/update/delete, +1 per bulk batch, +1 per
        compact), durable across evict/restore. KeyError for a tenant
        the pool has never seen."""
        t = self._tenants.get(tid)
        if t is None:
            raise KeyError(tid)
        return t.epoch

    def tenants(self) -> list[str]:
        return list(self._tenants)

    def resident_tenants(self) -> list[str]:
        return list(self._resident)

    # ---------------------------------------------------------- residency
    def _validate_id(self, s: str, what: str) -> None:
        if not isinstance(s, str) or not s or NS_SEP in s:
            raise ValueError(f"invalid {what}: {s!r} (non-empty string "
                             "without the namespace separator)")

    def _tenant_dir(self, tid: str) -> str:
        return os.path.join(self.root, "tenants",
                            urllib.parse.quote(tid, safe=""))

    def _touch(self, tid: str) -> None:
        self._resident[tid] = None
        self._resident.move_to_end(tid)

    def _empty_adapter(self):
        return FlatVectorIndex(metric=self.metric,
                               dim=self.dim or self._arena.dim, n_shards=1,
                               dtype=self.dtype,
                               rerank_factor=self.rerank_factor)

    def _adapter(self, tid: str, t: _TenantState) -> FlatVectorIndex:
        """The tenant's state as a real ``FlatVectorIndex`` — what the
        store snapshots/attaches. Bit-for-bit the index a never-pooled
        tenant would have: same canonical arrays (tenant insertion
        order, tombstones included), same epoch, same config."""
        fv = self._empty_adapter()
        keys, vecs, alive, enc, scales = self._arena.tenant_rows(tid)
        if keys:
            if self._codec.lossy:
                arrays = {"vectors_enc": self._codec.to_storage(enc),
                          "alive": alive}
                if scales is not None:
                    arrays["scales"] = scales
            else:
                arrays = {"vectors": vecs, "alive": alive}
            fv.restore_state(arrays, {"keys": keys, "epoch": t.epoch})
        else:
            fv._epoch = t.epoch
        return fv

    def _ensure_resident(self, tid: str, create: bool = False
                         ) -> _TenantState:
        self._validate_id(tid, "tenant id")
        t = self._tenants.get(tid)
        if t is None:
            store = None
            if self.root is not None:
                from repro.store import IndexStore
                store = IndexStore(self._tenant_dir(tid),
                                   page_bytes=4 << 20)
                if store.has_state():
                    t = _TenantState(store=store)
                    self._tenants[tid] = t
                    return self._admit(tid, t)
            if not create:
                raise KeyError(tid)
            t = _TenantState(store=store, resident=True)
            if store is not None:
                store.attach(self._empty_adapter())   # config.json now:
                # WAL-only restore needs it before any record replays
            self._tenants[tid] = t
            self._make_room(exclude=tid)
            self._touch(tid)
            return t
        if not t.resident:
            return self._admit(tid, t)
        self._touch(tid)
        return t

    def _make_room(self, exclude: str) -> None:
        while len(self._resident) >= self.max_resident:
            victim = next(t for t in self._resident if t != exclude)
            self.evict(victim)

    def _admit(self, tid: str, t: _TenantState) -> _TenantState:
        """Page a tenant back into the arena: bit-for-bit warm restore
        (snapshot + WAL replay via the store, DESIGN.md §7) adopted into
        fresh slabs."""
        self._make_room(exclude=tid)
        arrays = meta = None
        if t.store is not None and t.store.has_state():
            fv = t.store.load_index(expect_kind="flat")
            arrays, meta = fv.state_dict()
        elif t.spill is not None:
            arrays, meta = t.spill
        if arrays is not None and len(meta["keys"]):
            nskeys = [tenant_key(tid, k) for k in meta["keys"]]
            alive = np.asarray(arrays["alive"], bool)
            if self._codec.lossy:
                enc = self._codec.from_storage(arrays["vectors_enc"])
                scales = arrays.get("scales")
                vecs = self._codec.decode(enc, scales)
            else:
                enc = scales = None
                vecs = np.asarray(arrays["vectors"], np.float32)
            self._arena.adopt_rows(nskeys, vecs, alive, enc=enc,
                                   scales=scales)
            self.dim = self.dim or self._arena.dim
        if meta is not None:
            t.epoch = int(meta["epoch"])
        t.spill = None
        t.resident = True
        self._touch(tid)
        self.stats["admissions"] += 1
        return t

    def admit(self, tid: str) -> None:
        """Explicitly page a tenant in (queries/mutations do it
        implicitly)."""
        self._ensure_resident(tid)

    def evict(self, tid: str) -> None:
        """Page a tenant out: snapshot its state to the per-tenant store
        (or host spill), physically remove its rows from the arena
        (canonical arrays re-packed, freed slabs returned to the pool),
        and drop every derived device structure (the ``_drop_derived``
        residency contract — no stale block may outlive residency)."""
        t = self._tenants.get(tid)
        if t is None:
            raise KeyError(tid)
        if not t.resident:
            return
        self._snapshot_tenant(tid, t)
        self._arena.remove_rows(~self._arena.owner_mask(tid))
        self._drop_derived()
        t.resident = False
        self._resident.pop(tid, None)
        self.stats["evictions"] += 1

    def _snapshot_tenant(self, tid: str, t: _TenantState) -> None:
        fv = self._adapter(tid, t)
        if t.store is not None:
            t.store.snapshot(fv)
            t.since_snapshot = 0
            self.stats["snapshots"] += 1
        else:
            t.spill = fv.state_dict()

    def flush(self) -> None:
        """Snapshot every resident tenant (shutdown durability)."""
        for tid in list(self._resident):
            self._snapshot_tenant(tid, self._tenants[tid])

    def _drop_derived(self) -> None:
        """Invalidate every device-derived structure: packed arena
        blocks, gid maps, scale tables, and per-tenant slab tables.
        Called on evict (and implicitly by every arena mutation via
        ``_invalidate``)."""
        self._arena._invalidate()

    # ------------------------------------------------------------ mutation
    def _wal(self, t: _TenantState, op: str, meta: dict,
             arrays: dict | None = None) -> None:
        if t.store is not None:
            t.store.wal_append(op, epoch=t.epoch, meta=meta, arrays=arrays)

    def _finish_mutation(self, tid: str, t: _TenantState) -> None:
        t.epoch += 1
        self._epoch += 1
        t.since_snapshot += 1
        if (self.snapshot_every is not None
                and t.since_snapshot >= self.snapshot_every):
            self._snapshot_tenant(tid, t)

    def insert(self, tid: str, key: str, value) -> None:
        """Upsert one (key, vector) into a tenant's namespace."""
        self._validate_id(key, "key")
        t = self._ensure_resident(tid, create=True)
        v = np.asarray(value, np.float32)
        self._wal(t, "insert", {"key": key}, {"vec": v})
        self._arena.upsert(tenant_key(tid, key), v.reshape(-1))
        self.dim = self.dim or self._arena.dim
        self._finish_mutation(tid, t)

    def bulk_insert(self, tid: str, keys, values) -> None:
        """Batched upsert — ONE WAL record, last-wins on in-batch
        duplicates (same collapse the ``VectorIndex`` template does)."""
        values = np.asarray(values, np.float32)
        if len(keys) != len(values):
            raise ValueError("keys/values length mismatch")
        keys = list(keys)
        for k in keys:
            self._validate_id(k, "key")
        if len(set(keys)) != len(keys):
            last: dict = {}
            for i, k in enumerate(keys):
                last[k] = i
            keep = sorted(last.values())
            keys = [keys[i] for i in keep]
            values = values[keep]
        t = self._ensure_resident(tid, create=True)
        self._wal(t, "bulk_insert", {"keys": keys}, {"vec": values})
        self._arena.upsert_many([tenant_key(tid, k) for k in keys], values)
        self.dim = self.dim or self._arena.dim
        self._finish_mutation(tid, t)

    def update(self, tid: str, key: str, value) -> None:
        """Replace an existing key's vector. KeyError if absent."""
        t = self._ensure_resident(tid, create=True)
        if not self._arena.contains(tenant_key(tid, key)):
            raise KeyError(key)
        v = np.asarray(value, np.float32)
        self._wal(t, "update", {"key": key}, {"vec": v})
        self._arena.upsert(tenant_key(tid, key), v.reshape(-1))
        self._finish_mutation(tid, t)

    def delete(self, tid: str, key: str) -> None:
        """Soft-delete one key: never returned again, and only THIS
        tenant's epoch bumps (other tenants' caches stay valid)."""
        t = self._ensure_resident(tid)
        if not self._arena.contains(tenant_key(tid, key)):
            raise KeyError(key)
        self._wal(t, "delete", {"key": key})
        self._arena.tombstone(tenant_key(tid, key))
        self._finish_mutation(tid, t)

    def compact(self, tid: str) -> None:
        """Per-tenant secure delete (DESIGN.md §7, scoped): physically
        drop the tenant's tombstoned rows from the host arrays and the
        shared device blocks, publish a fresh snapshot of the compacted
        state, truncate the WAL (old records held the deleted vectors'
        insert payloads), and purge every older snapshot. After this the
        deleted rows' bytes — fp32, encoded, and scales — exist in no
        arena buffer, no slab, no page, and no WAL. Other tenants are
        untouched (their epochs do not move)."""
        t = self._ensure_resident(tid)
        dead = self._arena.owner_mask(tid) & ~self._arena.alive
        if dead.any():
            self._arena.remove_rows(~dead)
        t.epoch += 1                       # same bump a dedicated compact has
        self._epoch += 1
        t.since_snapshot = 0
        if t.store is not None:
            t.store.on_compact(self._adapter(tid, t))
        elif t.spill is not None:
            t.spill = None                 # spilled pre-compact state dies too

    # --------------------------------------------------------------- query
    def query_batch(self, tid: str, queries, k: int = 10, **kw):
        """One tenant, one dispatch: [B, D] -> (keys, dists) with the
        ``VectorIndex`` shape contract (None / INF padding). Under a
        lossy codec the slab scan is asymmetric and its float32 top-k
        over the stored rows is the result, as on a dedicated flat
        index (DESIGN.md §9)."""
        self._ensure_resident(tid)
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_batch expects [B, D], got {q.shape}")
        d, rows = self._arena.tenant_topk(tid, q, k)
        return self._rows_to_keys(rows, d, k)

    def query(self, tid: str, query, k: int = 10, **kw):
        q = np.asarray(query, np.float32)
        if q.ndim == 1:
            keys, d = self.query_batch(tid, q[None], k, **kw)
            return keys[0], d[0]
        return self.query_batch(tid, q, k, **kw)

    def query_batch_multi(self, queries, tenants, k: int = 10, **kw):
        """ONE logical dispatch for a batch whose rows belong to
        DIFFERENT tenants (the serving layer's cross-tenant tick,
        DESIGN.md §6): rows group by their tenant's padded slab width L —
        a group of one tenant runs the fused single-tenant kernel, a
        mixed group runs the per-query-gather path — and results come
        back in input order."""
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_batch_multi expects [B, D], "
                             f"got {q.shape}")
        tenants = list(tenants)
        if len(tenants) != q.shape[0]:
            raise ValueError("queries/tenants length mismatch")
        uniq = list(dict.fromkeys(tenants))
        if len(uniq) > self.max_resident:
            # more distinct tenants than can be co-resident: split the
            # tick into sub-batches of <= max_resident tenants and let
            # the LRU page between them — results stitch back in input
            # order, so callers never see the split
            out_keys: list = [None] * len(tenants)
            out_dists = [None] * len(tenants)
            for j in range(0, len(uniq), self.max_resident):
                grp = set(uniq[j:j + self.max_resident])
                idx = [i for i, t in enumerate(tenants) if t in grp]
                gk, gd = self.query_batch_multi(
                    q[idx], [tenants[i] for i in idx], k, **kw)
                gd = np.asarray(gd)
                for p, i in enumerate(idx):
                    out_keys[i] = gk[p]
                    out_dists[i] = gd[p]
            return out_keys, np.stack(out_dists)
        for tid in uniq:
            self._ensure_resident(tid)
        b = q.shape[0]
        out_d = np.full((b, k), INF, np.float32)
        out_g = np.full((b, k), -1, np.int64)
        # group rows by padded slab width; empty tenants raise like a
        # dedicated empty index would
        by_l: dict[int, list[int]] = {}
        for i, tid in enumerate(tenants):
            _, l_pad, live = self._arena.tenant_table(tid)
            if live == 0:
                raise ValueError("index is empty")
            by_l.setdefault(l_pad, []).append(i)
        for l_pad, rows_idx in by_l.items():
            g_tenants = [tenants[i] for i in rows_idx]
            g_q = q[rows_idx]
            if len(set(g_tenants)) == 1:
                d, g = self._arena.tenant_topk(g_tenants[0], g_q, k)
            else:
                tables = np.stack(
                    [self._arena.tenant_table(tid)[0]
                     for tid in g_tenants], axis=1)        # [S, B_g, L*R]
                d, g = self._arena.multi_topk(tables, g_q, k)
            out_d[rows_idx] = d
            out_g[rows_idx] = g
        return self._rows_to_keys(out_g, out_d, k)

    def _rows_to_keys(self, rows: np.ndarray, d: np.ndarray, k: int):
        keys = [[split_tenant_key(self._arena.key_of_row(int(r)))[1]
                 if r >= 0 else None for r in row] for row in rows]
        d = np.asarray(d)
        keys = [row_k[:k] for row_k in keys]
        return _pad_results(keys, d[:, :k], k)

    # ----------------------------------------------------------- introspect
    def size(self, tid: str) -> int:
        """Live keys of one tenant (pages it in if needed)."""
        self._ensure_resident(tid)
        return self._arena.tenant_live(tid)

    def contains(self, tid: str, key: str) -> bool:
        try:
            self._ensure_resident(tid)
        except KeyError:
            return False
        return self._arena.contains(tenant_key(tid, key))

    def keys(self, tid: str) -> list[str]:
        """One tenant's live keys in insertion order."""
        self._ensure_resident(tid)
        pre = tid + NS_SEP
        return [k.partition(NS_SEP)[2]
                for i, k in enumerate(self._arena.key_list)
                if self._arena.alive[i] and k.startswith(pre)]

    def pool_stats(self) -> dict:
        """Occupancy + paging counters (logging / bench)."""
        arena = self._arena
        slabs = sum(len(o) for o in arena._slab_owner)
        owned = sum(sum(o is not None for o in sh)
                    for sh in arena._slab_owner)
        return {**self.stats, "tenants": len(self._tenants),
                "resident": len(self._resident),
                "arena_rows": arena.row_count, "arena_live": arena.size,
                "slabs": slabs, "slabs_owned": owned,
                "slab_rows": self.slab_rows,
                "arena_bytes": arena.arena_device_bytes()}
