"""MeMemo-parity public API (paper §2.1, Code 1) — now a full
``VectorIndex`` backend with real mutation semantics (DESIGN.md §1/§3).

TypeScript original:
    const index = new HNSW({ distanceFunction: 'cosine' });
    await index.bulkInsert(keys, values);
    const { keys, distances } = await index.query(query, k);
    index.exportIndex() / loadIndex()

Python equivalent (camelCase aliases kept for 1:1 parity):
    index = HNSW(distance_function="cosine", M=5, ef_construction=20)
    index.bulk_insert(keys, values)
    index.update("doc-3", new_vec)       # delete + reinsert, same key
    index.delete("doc-7")                # tombstone: excluded from results
    keys, distances = index.query(query, k=10)
    index.export_index(path); HNSW.load_index(path)

Mutation model: the ``SequentialBuilder`` is the canonical mutable host
graph. Deletes are soft (a tombstone mask threaded through the device-side
beam search — deleted ids stay traversable, hnswlib-style); updates are
delete + reinsert under the same key. After the first query materialises a
resident ``DeviceGraph`` (capacity-padded, fixed shapes), later mutations
upload only the builder's dirty-row journal via ``apply_row_updates``
instead of re-converting the whole graph (DESIGN.md §3).

Sharded operation (``n_shards > 1``, DESIGN.md §8): a navigable
small-world graph cannot be row-partitioned without breaking its search
invariants, so the sharded HNSW is a FAISS/Milvus-style segment set —
each shard owns an independent graph over its hash-routed keys. CRUD
routes to the owning shard (same ``shard_of_key`` as every backend), ANN
queries run the lock-step beam search on every shard's graph in ONE
compiled dispatch (the stacked segment fan-out, ``core/stacked.py``,
cached per mutation epoch) and merge in-program, and the exact/flat
phase queries epoch-cached device-resident blocks
(``build_exact_blocks``/``exact_topk_blocks``). Per-shard graphs are smaller
(N/S rows -> cheaper expansions) and per-shard ANN results are merged
candidates, so cross-shard-count parity holds for ``exact_query`` but
``query_batch`` is parity-at-the-recall-level only — the per-shard
graphs are different (valid) indexes. A global insertion-sequence table
rides in ``state_dict`` so a snapshot can be RESHARDED on restore:
rows replay into fresh per-shard builders in canonical order.
"""
from __future__ import annotations

import numpy as np

from repro.core import hnsw as jhnsw
from repro.core import hnsw_build as build
from repro.core import stacked as jstacked
from repro.core.codec import (check_codec_arrays as _check_codec_arrays,
                              effective_rerank, get_codec, rerank_exact)
from repro.core.dispatch import span
from repro.core.flat import FlatIndex
from repro.core.hnsw_build import normalize_rows
from repro.core.index import VectorIndex
from repro.core.sharded import (build_exact_blocks, exact_topk_blocks,
                                shard_mesh, shard_of_key)


class HNSW(VectorIndex):
    kind = "hnsw"

    def __init__(self, distance_function: str = "cosine", *, M: int = 16,
                 ef_construction: int = 200, ef_search: int = 64,
                 seed: int = 0, use_bulk_build: bool = False,
                 n_shards: int = 1, dtype: str = "fp32",
                 rerank_factor: int | None = None,
                 beam_impl: str = "fused"):
        if distance_function not in ("cosine", "ip", "l2"):
            raise ValueError(f"unknown distanceFunction {distance_function!r}")
        if beam_impl not in ("fused", "jnp"):
            raise ValueError(f"unknown beam_impl {beam_impl!r}; "
                             "expected 'fused' or 'jnp'")
        self.metric = distance_function
        # layer-0 beam implementation (DESIGN.md §12): "fused" runs the
        # whole ef-beam as one kernel launch; "jnp" is the per-hop
        # while_loop reference (the parity oracle)
        self.beam_impl = beam_impl
        self.M = M
        self.ef_construction = ef_construction
        self.ef_search = ef_search
        self.seed = seed
        self.use_bulk_build = use_bulk_build
        self.n_shards = int(n_shards)
        # row-storage codec (DESIGN.md §9): lossy codecs quantize each row
        # once at ingest (after metric normalization); the encoded bytes
        # are canonical — the device graph and snapshot pages hold them,
        # the builder's fp32 vectors are their exact decode, and ANN
        # queries over-fetch k·rerank_factor then rerank exactly in fp32
        self.dtype = str(dtype)
        self.rerank_factor = rerank_factor
        self._codec = get_codec(self.dtype)
        self._keys: list[str] = []                 # node id -> key
        self._key2id: dict[str, int] = {}          # live keys only
        self._deleted = np.zeros(0, bool)          # tombstones, capacity-sized
        self._builder: build.SequentialBuilder | None = None
        # canonical encoded rows [n, D] + per-row scales [n] (lossy only;
        # node-id aligned with the builder, appended per insert)
        self._enc: np.ndarray | None = None
        self._scales: np.ndarray | None = None
        # compat only: external code reads `idx._graph or idx._builder.graph()`
        self._graph: build.HNSWGraph | None = None
        self._device_graph: jhnsw.DeviceGraph | None = None
        self._deleted_dirty = False
        # sharded segment set (n_shards > 1): child graphs + routing +
        # the canonical insertion-sequence table (DESIGN.md §8)
        self._shards: list["HNSW"] = []
        self._key2shard: dict[str, int] = {}
        self._seq: dict[str, int] = {}
        self._next_seq = 0
        # epoch-keyed derived device state (sharded only, DESIGN.md §8):
        # the stacked segment set, the gid-aligned fp32 rerank rows, and
        # the exact-phase placed blocks. Mutations invalidate via the
        # epoch key; restores drop them explicitly (_drop_derived) since
        # a restore may land on the same epoch with different rows.
        self._stacked_cache: tuple[int, jstacked.StackedGraphs] | None = None
        self._rerank_rows_cache: tuple[int, np.ndarray] | None = None
        self._exact_cache: tuple | None = None
        if self.n_shards > 1:
            self._shards = [
                HNSW(distance_function=distance_function, M=M,
                     ef_construction=ef_construction, ef_search=ef_search,
                     seed=seed + j, use_bulk_build=False, n_shards=1,
                     dtype=self.dtype, rerank_factor=rerank_factor,
                     beam_impl=beam_impl)
                for j in range(self.n_shards)]

    # --------------------------------------------------- shard plumbing
    @property
    def shard_count(self) -> int:
        return self.n_shards

    def _mirror(self, child: "HNSW", fn, *args) -> None:
        """Run a child-shard impl and mirror its epoch delta onto the
        outer index, so the outer ``mutation_epoch`` advances exactly as
        the 1-shard index would for the same op (cache-invalidation
        parity across shard counts, DESIGN.md §6/§8)."""
        before = child._epoch
        fn(*args)
        self._epoch += child._epoch - before

    # ------------------------------------------------------------ mutation
    def _quantize(self, v: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray | None, float | None]:
        """Put one raw row in its final stored form (DESIGN.md §9):
        metric normalization, then ONE codec encode whose decode becomes
        the stored fp32 row — so the encoded bytes are canonical and the
        snapshot round-trip is bit-stable."""
        if self.metric == "cosine":
            v = v / max(float(np.linalg.norm(v)), 1e-12)
        enc, scales = self._codec.encode(v[None])
        v = self._codec.decode(enc, scales)[0]
        return v, enc[0], (None if scales is None else scales[0])

    def _append_enc(self, enc_row: np.ndarray,
                    scale: float | None) -> None:
        if self._enc is None:
            self._enc = np.zeros((0, enc_row.shape[-1]),
                                 self._codec.enc_dtype)
        self._enc = np.concatenate([self._enc, enc_row[None]])
        if scale is not None:
            if self._scales is None:
                self._scales = np.zeros(0, np.float32)
            self._scales = np.concatenate(
                [self._scales, np.asarray([scale], np.float32)])

    def _insert_node(self, key: str, v: np.ndarray,
                     enc_row: np.ndarray | None,
                     scale: float | None) -> None:
        """Commit one ALREADY-FINAL row (quantized by ``_quantize`` or
        carried over by compaction) to the builder + enc side arrays."""
        if self._builder is None:
            self._builder = build.SequentialBuilder(
                v.shape[-1], M=self.M, ef_construction=self.ef_construction,
                metric=self.metric, seed=self.seed)
        node = self._builder.insert(v, prenormalized=True)
        assert node == len(self._keys)
        self._keys.append(key)
        self._key2id[key] = node
        if enc_row is not None:
            self._append_enc(enc_row, scale)
        self._bump_epoch()

    def _insert_impl(self, key: str, value: np.ndarray) -> None:
        """Upsert one (key, vector); existing keys are updated in place."""
        if self.n_shards > 1:
            s = shard_of_key(key, self.n_shards)
            self._mirror(self._shards[s], self._shards[s]._insert_impl,
                         key, np.asarray(value, np.float32))
            self._key2shard[key] = s
            self._seq[key] = self._next_seq
            self._next_seq += 1
            return
        if key in self._key2id:
            self._delete_impl(key)
        v = np.asarray(value, np.float32)
        if self._codec.lossy:
            v, enc_row, scale = self._quantize(v)
            self._insert_node(key, v, enc_row, scale)
            return
        if self._builder is None:
            self._builder = build.SequentialBuilder(
                v.shape[-1], M=self.M, ef_construction=self.ef_construction,
                metric=self.metric, seed=self.seed)
        node = self._builder.insert(v)
        assert node == len(self._keys)
        self._keys.append(key)
        self._key2id[key] = node
        self._bump_epoch()

    def _bulk_insert_impl(self, keys: list[str], values: np.ndarray) -> None:
        if self.n_shards > 1:
            # routed inserts in global order: deterministic per-shard
            # insertion sequences regardless of batch boundaries
            if self.use_bulk_build and self._row_count() == 0:
                # epoch parity with the 1-shard bulk-build path, which
                # bumps ONCE for the whole first batch — the WAL replays
                # one record per template call, so the epoch delta per
                # record must match at every shard count or reshard-
                # restore skips/faults on the records that follow
                before = self._epoch
                for k, v in zip(keys, values):
                    self._insert_impl(k, v)
                self._epoch = before + 1
                return
            for k, v in zip(keys, values):
                self._insert_impl(k, v)
            return
        if self.use_bulk_build and self._builder is None:
            values = np.asarray(values, np.float32)
            if self._codec.lossy:
                # normalize + quantize the whole batch once; the graph is
                # built over the decoded (final, stored) rows (§9)
                if self.metric == "cosine":
                    values = normalize_rows(values)
                enc, scales = self._codec.encode(values)
                values = self._codec.decode(enc, scales)
                self._enc = enc
                self._scales = scales
            self._adopt_bulk_graph(keys, values,
                                   prenormalized=self._codec.lossy)
            return
        for k, v in zip(keys, values):
            self._insert_impl(k, v)

    def _adopt_bulk_graph(self, keys: list[str], values: np.ndarray,
                          prenormalized: bool) -> None:
        """Build a whole graph through the device-resident bulk ingest
        (DESIGN.md §13) and adopt it as mutable builder state, so a
        LATER bulk_insert / insert appends instead of silently replacing
        the graph. ``values`` must already be final stored rows when
        ``prenormalized`` (codec decode, §9)."""
        g = build.bulk_build(
            values, M=self.M, ef_construction=self.ef_construction,
            metric=self.metric, seed=self.seed,
            prenormalized=prenormalized, beam_impl=self.beam_impl)
        self._builder = build.SequentialBuilder.from_graph(
            g, ef_construction=self.ef_construction, seed=self.seed)
        self._keys = list(keys)
        self._key2id = {k: i for i, k in enumerate(self._keys)}
        self._device_graph = None
        self._bump_epoch()

    bulkInsert = VectorIndex.bulk_insert   # TS-parity alias

    def _update_impl(self, key: str, value: np.ndarray) -> None:
        """Replace the vector of an existing key (delete + reinsert)."""
        self._insert_impl(key, value)

    def _delete_impl(self, key: str) -> None:
        """Soft-delete: tombstone the row; it stays traversable but is
        never returned from query/exact_query again."""
        if self.n_shards > 1:
            s = self._key2shard.pop(key)           # KeyError if absent
            self._seq.pop(key, None)
            self._mirror(self._shards[s], self._shards[s]._delete_impl, key)
            return
        node = self._key2id.pop(key)               # KeyError if absent
        self._ensure_tombstones()
        self._deleted[node] = True
        self._deleted_dirty = True
        self._bump_epoch()

    def _compact_impl(self) -> None:
        """Physically drop tombstoned rows (DESIGN.md §7): rebuild the
        graph from scratch over live vectors only. Deleted rows stop
        existing host-side — this is the expensive half of secure delete
        (tombstoning stays the cheap everyday path); the store layer
        rewrites the on-disk pages afterwards."""
        if self.n_shards > 1:
            # child epochs are internal; the OUTER delta must match what
            # the 1-shard path produces for the same live set (one bump
            # per reinserted row, or one bump when nothing is live) —
            # naive mirroring would add +1 per EMPTY child and break
            # epoch parity across shard counts
            live_total = self.size
            for child in self._shards:
                child._compact_impl()
            self._epoch += live_total if live_total else 1
            return
        if self._builder is None:
            self._bump_epoch()
            return
        self._ensure_tombstones()
        n = self._builder.n
        live = np.flatnonzero(~self._deleted[:n])
        vecs = self._builder.vectors[live].copy()
        keys = [self._keys[i] for i in live]
        # carry the CANONICAL encoded rows through the rebuild: a deleted
        # row's encoded bytes + scale die here with its fp32 bytes
        # (secure delete, §9), while live rows keep their exact encoding
        # (re-quantizing an already-quantized row would perturb bytes)
        enc = self._enc[live].copy() if self._enc is not None else None
        scl = self._scales[live].copy() if self._scales is not None else None
        self._builder = None                       # fresh graph + fresh RNG
        self._keys = []
        self._key2id = {}
        self._deleted = np.zeros(0, bool)
        self._enc = None
        self._scales = None
        self._device_graph = None
        self._deleted_dirty = False
        if self._codec.lossy:
            for i, (k, v) in enumerate(zip(keys, vecs)):
                self._insert_node(k, v, enc[i],    # bumps epoch per insert
                                  None if scl is None else scl[i])
        else:
            for k, v in zip(keys, vecs):
                self._insert_impl(k, v)            # bumps epoch per insert
        if not keys:
            self._bump_epoch()

    def _ensure_tombstones(self):
        cap = self._builder.vectors.shape[0] if self._builder is not None else 0
        if self._deleted.shape[0] < cap:
            pad = np.zeros(cap - self._deleted.shape[0], bool)
            self._deleted = np.concatenate([self._deleted, pad])

    # ----------------------------------------------------- device residency
    def _enc_capacity(self, cap: int
                      ) -> tuple[np.ndarray | None, np.ndarray | None]:
        """Canonical encoded rows padded to the builder's capacity view
        (zeros beyond ``n`` — matching the builder's zero rows), the
        shape the device graph and snapshots use (§9)."""
        if self._enc is None:
            return None, None
        n, d = self._enc.shape
        enc = np.zeros((cap, d), self._codec.enc_dtype)
        enc[:n] = self._enc
        scl = None
        if self._scales is not None:
            scl = np.zeros(cap, np.float32)
            scl[:n] = self._scales
        return enc, scl

    def _dg(self) -> jhnsw.DeviceGraph:
        """Resident device graph, synced incrementally when possible.
        Under a lossy codec the resident vectors are the ENCODED rows
        (+ scale table): HBM holds ``codec.bytes_per_vector`` per row and
        every distance decodes inside the gather kernel (§9)."""
        if self._builder is None:
            raise ValueError("index is empty")
        b = self._builder
        self._ensure_tombstones()
        g = b.graph_full_capacity(b.max_level_cap)   # fixed [12, cap, M] upper
        dg = self._device_graph
        if dg is None or not dg.fits(g):
            # first upload, or capacity growth: full conversion
            enc, scl = self._enc_capacity(g.vectors.shape[0])
            self._device_graph = jhnsw.to_device_graph(
                g, self._deleted, enc=enc, scales=scl)
            b.journal.clear()
            self._deleted_dirty = False
        elif b.journal or self._deleted_dirty or dg.max_level != g.max_level:
            # incremental: only dirty rows travel to the device. The
            # scatter indexes enc/scales by dirty row id (< n), so the
            # canonical [n, D] arrays are handed over AS-IS — building
            # the capacity-padded view here would make every sync O(N)
            # host work instead of O(|dirty|)
            self._device_graph = jhnsw.apply_row_updates(
                dg, g, b.journal,
                self._deleted if self._deleted_dirty else None,
                enc=self._enc, scales=self._scales)
            b.journal.clear()
            self._deleted_dirty = False
        return self._device_graph

    # --------------------------------------------------------------- query
    def query_batch(self, queries, k: int = 10, ef: int | None = None):
        """One lock-step device search for the whole [B, D] batch.

        All B queries advance together through ``search_graph`` (DESIGN.md
        §2); the compiled program is cached per (B, k, ef) shape, which is
        why the serving layer coalesces into power-of-two B buckets.

        Sharded: the same lock-step search runs on every shard's graph
        (each N/S-row graph is a cheaper search) and the per-shard
        candidates merge by distance (DESIGN.md §8).
        """
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_batch expects [B, D], got {q.shape}")
        if self.n_shards > 1:
            return self._query_batch_sharded(q, k, ef)
        rows = q.shape[0]
        rf = effective_rerank(self._codec, self.rerank_factor)
        with span("index.scan", rows=rows):
            ids, dists = jhnsw.search_graph(self._dg(), q, k=k * rf,
                                            ef=ef or self.ef_search,
                                            beam_impl=self.beam_impl)
        with span("index.fetch", rows=rows):
            ids, dists = np.asarray(ids), np.asarray(dists)
        if rf > 1:
            # over-fetched beam candidates rerank exactly in fp32 against
            # the canonical host rows (§9); beam already dropped
            # tombstoned ids, so every candidate is live
            with span("index.rerank", rows=rows):
                n = self._builder.n
                dists, ids = rerank_exact(self._builder.vectors[:n], q, ids,
                                          k, metric=self.metric)
        with span("index.keys", rows=rows):
            keys = [[self._keys[i] if i >= 0 else None for i in row]
                    for row in ids]
        return keys, dists

    def _drop_derived(self) -> None:
        """Drop the epoch-keyed derived device state. Needed on restore:
        a restored index can land on the SAME epoch number as the cached
        state while holding different rows, so the epoch key alone is
        not a safe invalidator there."""
        self._stacked_cache = None
        self._rerank_rows_cache = None
        self._exact_cache = None

    def _stacked(self) -> jstacked.StackedGraphs:
        """Epoch-cached stacked segment set: per-shard resident device
        graphs stacked along [S, ...] (core/stacked.py). Rebuilt only
        when the index mutates; ``_dg()`` keeps each child's resident
        graph synced incrementally, so a rebuild after a small mutation
        moves O(dirty) host bytes, then pads + stacks on device."""
        if (self._stacked_cache is not None
                and self._stacked_cache[0] == self._epoch):
            return self._stacked_cache[1]
        graphs = [child._dg() if child._builder is not None else None
                  for child in self._shards]
        st = jstacked.stack_device_graphs(graphs, shard_mesh(self.n_shards))
        self._stacked_cache = (self._epoch, st)
        return st

    def _rerank_rows(self, st: jstacked.StackedGraphs) -> np.ndarray:
        """Epoch-cached gid-aligned canonical fp32 rows [S*cap, D]: the
        stacked search's global ids index this array directly, so the
        lossy-codec rerank (DESIGN.md §9) needs no id remapping."""
        if (self._rerank_rows_cache is not None
                and self._rerank_rows_cache[0] == self._epoch):
            return self._rerank_rows_cache[1]
        dim = int(st.vectors.shape[-1])
        rows = np.zeros((self.n_shards * st.cap, dim), np.float32)
        for s, child in enumerate(self._shards):
            if child._builder is not None:
                n = child._builder.n
                rows[s * st.cap:s * st.cap + n] = child._builder.vectors[:n]
        self._rerank_rows_cache = (self._epoch, rows)
        return rows

    def _query_batch_sharded(self, q: np.ndarray, k: int, ef: int | None):
        """One compiled dispatch at any shard count: per-shard beam
        search + in-program tree merge over the epoch-cached stacked
        segment set (core/stacked.py). Lossy codecs over-fetch
        ``k * rerank_factor`` per shard, merge in-program, and rerank
        the merged candidates exactly in fp32 against the gid-aligned
        canonical rows."""
        rows = q.shape[0]
        rf = effective_rerank(self._codec, self.rerank_factor)
        kf = k * rf
        with span("index.scan", rows=rows):
            st = self._stacked()
            d, gid = jstacked.search_stacked(st, q, kf,
                                             max(ef or self.ef_search, kf),
                                             beam_impl=self.beam_impl)
        with span("index.fetch", rows=rows):
            d, gid = np.asarray(d), np.asarray(gid)
        if rf > 1:
            with span("index.rerank", rows=rows):
                d, gid = rerank_exact(self._rerank_rows(st), q, gid, k,
                                      metric=self.metric)
        cap = st.cap
        with span("index.keys", rows=rows):
            keys = [[self._shards[int(g) // cap]._keys[int(g) % cap]
                     if g >= 0 else None for g in row] for row in gid]
        return keys, d

    def _query_batch_sharded_loop(self, q: np.ndarray, k: int,
                                  ef: int | None):
        """Per-child Python fan-out (S dispatches + host merge): the
        pre-compiled-path implementation, kept as the parity oracle for
        the stacked fan-out (tests/test_sharded.py)."""
        parts = [(child.query_batch(q, k=k, ef=ef))
                 for child in self._shards if child._builder is not None]
        if not parts:
            raise ValueError("index is empty")
        d_cat = np.concatenate([d for _, d in parts], axis=1)     # [B, C*k]
        k_cat = [sum((pk[b] for pk, _ in parts), [])
                 for b in range(q.shape[0])]
        order = np.argsort(d_cat, axis=1, kind="stable")[:, :k]
        dists = np.take_along_axis(d_cat, order, axis=1)
        keys = [[k_cat[b][j] for j in order[b]] for b in range(q.shape[0])]
        return keys, dists

    def exact_query(self, query, k: int = 10):
        """Brute-force oracle over the same LIVE vectors -> (keys, dists).

        Sharded: the flat phase queries the epoch-cached device blocks —
        every shard scans its own live rows with the fused kernel and the
        per-shard top-k merges through the ppermute tree
        (``exact_topk_blocks``, DESIGN.md §8), so exact results are
        shard-count independent and steady-state calls upload nothing."""
        if self.n_shards > 1:
            return self._exact_query_sharded(query, k)
        if self._builder is None:
            raise ValueError("index is empty")
        self._ensure_tombstones()
        n = self._builder.n
        live = np.flatnonzero(~self._deleted[:n])
        if live.size == 0:
            raise ValueError("index is empty")
        flat = FlatIndex(vectors=np.asarray(self._builder.vectors[live]),
                         metric=self.metric)
        q = np.asarray(query, np.float32)
        squeeze = q.ndim == 1
        if squeeze:
            q = q[None]
        d, i = flat.query(q, min(k, live.size))
        d, i = np.asarray(d), np.asarray(i)
        keys = [[self._keys[int(live[j])] for j in row] for row in i]
        if squeeze:
            return keys[0], d[0]
        return keys, d

    def _live_by_seq(self) -> list[tuple[int, str, int, int]]:
        """Live rows in canonical (insertion-sequence) order:
        [(seq, key, shard, node)]."""
        items = []
        for s, child in enumerate(self._shards):
            for key, node in child._key2id.items():
                items.append((self._seq[key], key, s, node))
        items.sort()
        return items

    def _exact_placed(self):
        """Epoch-cached exact-phase blocks: (items, placed). The host
        repack + ``device_put`` of the [S, R, D] block array happens once
        per mutation epoch (same invalidation contract as the serve-layer
        LRU); steady-state exact search then queries resident blocks
        with zero host-byte movement (``exact_topk_blocks``)."""
        if (self._exact_cache is not None
                and self._exact_cache[0] == self._epoch):
            return self._exact_cache[1], self._exact_cache[2]
        items = self._live_by_seq()
        # canonical gid = rank in insertion order, grouped per shard in
        # one O(live) pass
        ranks: list[list[int]] = [[] for _ in range(self.n_shards)]
        nodes: list[list[int]] = [[] for _ in range(self.n_shards)]
        for rank, (_, _, s, node) in enumerate(items):
            ranks[s].append(rank)
            nodes[s].append(node)
        dim = 0
        groups = []
        for s, child in enumerate(self._shards):
            if child._builder is not None:
                dim = int(child._builder.vectors.shape[1])
            if ranks[s] and child._builder is not None:
                vecs = np.asarray(child._builder.vectors[nodes[s]],
                                  np.float32)
            else:
                vecs = np.zeros((0, 0), np.float32)
            groups.append((vecs, np.asarray(ranks[s], np.int32)))
        # lossy codecs: rows are already in final stored form (normalized
        # BEFORE quantization, §9) — re-normalizing the quantized rows
        # here would score different values than the 1-shard exact path
        placed = build_exact_blocks(
            groups, dim, normalize=(self.metric == "cosine"
                                    and not self._codec.lossy))
        self._exact_cache = (self._epoch, items, placed)
        return items, placed

    def _exact_query_sharded(self, query, k: int):
        items, placed = self._exact_placed()
        if not items:
            raise ValueError("index is empty")
        q = np.asarray(query, np.float32)
        squeeze = q.ndim == 1
        if squeeze:
            q = q[None]
        d, g = exact_topk_blocks(placed, q, min(k, len(items)),
                                 metric=self.metric)
        keys = [[items[int(j)][1] if j >= 0 else None for j in row]
                for row in g]
        if squeeze:
            return keys[0], d[0]
        return keys, d

    @property
    def size(self) -> int:
        if self.n_shards > 1:
            return len(self._key2shard)
        return len(self._key2id)

    def _contains(self, key: str) -> bool:
        if self.n_shards > 1:
            return key in self._key2shard
        return key in self._key2id

    def _row_count(self) -> int:
        if self.n_shards > 1:
            return sum(c._row_count() for c in self._shards)
        return self._builder.n if self._builder is not None else 0

    def keys(self) -> list[str]:
        if self.n_shards > 1:
            return [k for _, k in sorted(
                (self._seq[k], k) for k in self._key2shard)]
        n = self._builder.n if self._builder is not None else 0
        self._ensure_tombstones()
        return [self._keys[i] for i in range(n) if not self._deleted[i]]

    def shard_stats(self) -> list[dict]:
        # same convention at every shard count: slots = rows ever held
        # (tombstones included), free = tombstoned, live = slots - free
        if self.n_shards == 1:
            return [{"shard": 0, "slots": self._row_count(),
                     "free": self._row_count() - self.size,
                     "live": self.size}]
        return [{"shard": s, "slots": c._row_count(),
                 "free": c._row_count() - c.size, "live": c.size}
                for s, c in enumerate(self._shards)]

    # ------------------------------------------------------- persistence
    def config_dict(self) -> dict:
        return {"metric": self.metric, "M": self.M,
                "ef_construction": self.ef_construction,
                "ef_search": self.ef_search, "seed": self.seed,
                "use_bulk_build": self.use_bulk_build,
                "n_shards": self.n_shards, "dtype": self.dtype,
                "rerank_factor": self.rerank_factor,
                "beam_impl": self.beam_impl}

    def state_dict(self) -> tuple[dict, dict]:
        """Full mutation-determined host state, CAPACITY-padded: the
        builder's fixed-shape arrays go to disk as-is, so restore adopts
        them directly and the first query does one plain device upload —
        no graph rebuild (the expensive path the paper measures at 94 min
        for 1M rows). The builder RNG state rides along so WAL replay of
        later inserts draws the exact same levels (DESIGN.md §7).

        An index with no builder (nothing ever inserted, or compacted
        down to zero live rows) serializes as the empty state — a store
        must still be able to snapshot it: compacting away the LAST
        document is precisely the secure-delete case.

        Sharded: one namespaced sub-state per shard plus the canonical
        insertion-sequence table — which is what lets a snapshot restore
        at a DIFFERENT shard count (rows replay into fresh builders in
        canonical order; DESIGN.md §8)."""
        if self.n_shards > 1:
            arrays: dict = {}
            shard_meta = []
            for j, child in enumerate(self._shards):
                a, m = child.state_dict()
                for name, v in a.items():
                    arrays[f"s{j}__{name}"] = v
                shard_meta.append(m)
            meta = {"n_shards": self.n_shards, "epoch": self._epoch,
                    "shards": shard_meta,
                    "seq": sorted(self._seq.items(), key=lambda kv: kv[1]),
                    "next_seq": self._next_seq}
            return arrays, meta
        if self._builder is None:
            arrays = {"levels": np.zeros(0, np.int32),
                      "neighbors0": np.zeros((0, 2 * self.M), np.int32),
                      "upper": np.zeros((0, 0, self.M), np.int32),
                      "deleted": np.zeros(0, bool)}
            if self._codec.lossy:
                arrays["vectors_enc"] = self._codec.to_storage(
                    np.zeros((0, 0), self._codec.enc_dtype))
                if self._codec.uses_scales:
                    arrays["scales"] = np.zeros(0, np.float32)
            else:
                arrays["vectors"] = np.zeros((0, 0), np.float32)
            meta = {"keys": [], "epoch": self._epoch, "n": 0, "entry": -1,
                    "max_level": -1, "max_level_cap": 12, "rng_state": None}
            return arrays, meta
        b = self._builder
        self._ensure_tombstones()
        arrays = {"levels": b.levels, "neighbors0": b.neighbors0,
                  "upper": b.upper, "deleted": self._deleted}
        if self._codec.lossy:
            # persist the CANONICAL encoded rows + scales, capacity-padded
            # like the builder arrays: ≈4x smaller pages, and restore
            # decodes back to the exact builder vectors (§9)
            enc, scl = self._enc_capacity(b.vectors.shape[0])
            arrays["vectors_enc"] = self._codec.to_storage(enc)
            if scl is not None:
                arrays["scales"] = scl
        else:
            arrays["vectors"] = b.vectors
        meta = {"keys": list(self._keys), "epoch": self._epoch,
                "n": int(b.n), "entry": int(b.entry),
                "max_level": int(b.max_level),
                "max_level_cap": int(b.max_level_cap),
                "rng_state": b.rng.bit_generator.state}
        return arrays, meta

    def restore_state(self, arrays: dict, meta: dict) -> None:
        _check_codec_arrays(self._codec, arrays, self.kind)
        rec_shards = int(meta.get("n_shards", 1))
        if rec_shards != self.n_shards:
            # shard-count changed between snapshot and restore: replay the
            # canonical row sequence into the new layout (DESIGN.md §8).
            self._restore_resharded(arrays, meta, rec_shards)
            return
        if self.n_shards > 1:
            for j, (child, m) in enumerate(zip(self._shards, meta["shards"])):
                sub = {name[len(f"s{j}__"):]: v for name, v in arrays.items()
                       if name.startswith(f"s{j}__")}
                child.restore_state(sub, m)
            self._key2shard = {k: s for s, c in enumerate(self._shards)
                               for k in c._key2id}
            self._seq = {k: int(v) for k, v in meta["seq"]}
            self._next_seq = int(meta["next_seq"])
            self._epoch = int(meta["epoch"])
            self._drop_derived()
            return
        if meta["n"] == 0:                # empty state: no builder yet
            self._builder = None
            self._keys = []
            self._key2id = {}
            self._deleted = np.zeros(0, bool)
            self._enc = None
            self._scales = None
            self._epoch = int(meta["epoch"])
            self._device_graph = None
            self._deleted_dirty = False
            return
        n = int(meta["n"])
        if self._codec.lossy:
            # adopt the stored ENCODED rows as canonical and decode the
            # builder's fp32 side from them — never re-encode (§9)
            enc_cap = self._codec.from_storage(arrays["vectors_enc"])
            scl_cap = (np.asarray(arrays["scales"], np.float32)
                       if "scales" in arrays else None)
            vectors = self._codec.decode(enc_cap, scl_cap)
            self._enc = np.ascontiguousarray(enc_cap[:n])
            self._scales = (None if scl_cap is None
                            else np.ascontiguousarray(scl_cap[:n]))
        else:
            vectors = np.asarray(arrays["vectors"], np.float32)
            self._enc = None
            self._scales = None
        b = build.SequentialBuilder(
            vectors.shape[1], M=self.M,
            ef_construction=self.ef_construction, metric=self.metric,
            capacity=vectors.shape[0], max_level_cap=meta["max_level_cap"],
            seed=self.seed)
        b.vectors = vectors
        b.levels = np.asarray(arrays["levels"], np.int32)
        b.neighbors0 = np.asarray(arrays["neighbors0"], np.int32)
        b.upper = np.asarray(arrays["upper"], np.int32)
        b.n = n
        b.entry = int(meta["entry"])
        b.max_level = int(meta["max_level"])
        b.rng.bit_generator.state = meta["rng_state"]
        self._builder = b
        self._keys = list(meta["keys"])
        self._deleted = np.asarray(arrays["deleted"], bool).copy()
        self._key2id = {k: i for i, k in enumerate(self._keys)
                        if not self._deleted[i]}
        self._epoch = int(meta["epoch"])
        self._device_graph = None
        self._deleted_dirty = False

    def _recorded_rows(self, arrays: dict, prefix: str = ""):
        """Recorded rows -> (fp32 vectors, encoded rows | None,
        scales | None), whatever codec wrote them (§9)."""
        if f"{prefix}vectors" in arrays:
            return (np.asarray(arrays[f"{prefix}vectors"], np.float32),
                    None, None)
        enc = self._codec.from_storage(arrays[f"{prefix}vectors_enc"])
        scl = arrays.get(f"{prefix}scales")
        return self._codec.decode(enc, scl), enc, scl

    def _canonical_rows(self, arrays: dict, meta: dict, rec_shards: int
                        ) -> list[tuple]:
        """Live rows of a recorded state in canonical insertion order:
        [(seq, key, vector, enc_row|None, scale|None)] — the
        shard-layout-independent view, encodings included so a reshard
        replay keeps the canonical bytes instead of re-quantizing (§9)."""
        def _row(vecs, enc, scl, node):
            return (vecs[node],
                    None if enc is None else enc[node],
                    None if scl is None else scl[node])

        rows: list[tuple] = []
        if rec_shards == 1:
            n = int(meta["n"])
            deleted = np.asarray(arrays["deleted"], bool)
            vecs, enc, scl = self._recorded_rows(arrays)
            for node in range(n):
                if not deleted[node]:
                    rows.append((node, meta["keys"][node],
                                 *_row(vecs, enc, scl, node)))
            return rows
        seqmap = {k: int(v) for k, v in meta["seq"]}
        for j, m in enumerate(meta["shards"]):
            n = int(m["n"])
            if n == 0:
                continue
            deleted = np.asarray(arrays[f"s{j}__deleted"], bool)
            vecs, enc, scl = self._recorded_rows(arrays, prefix=f"s{j}__")
            for node in range(n):
                key = m["keys"][node]
                if not deleted[node]:
                    rows.append((seqmap[key], key,
                                 *_row(vecs, enc, scl, node)))
        rows.sort(key=lambda r: r[0])
        return rows

    def _insert_canonical(self, key: str, vec: np.ndarray,
                          enc_row: np.ndarray | None,
                          scale: float | None) -> None:
        """Reshard-replay insert of an already-final row: routes like
        ``_insert_impl`` but ADOPTS the recorded encoding instead of
        re-quantizing — re-encoding a decoded row is not guaranteed to
        reproduce the same scale bytes, and the canonical encoding must
        survive a reshard (§9). fp32 rows take the historical replay
        path unchanged."""
        if self.n_shards > 1:
            s = shard_of_key(key, self.n_shards)
            self._mirror(self._shards[s], self._shards[s]._insert_canonical,
                         key, vec, enc_row, scale)
            self._key2shard[key] = s
            self._seq[key] = self._next_seq
            self._next_seq += 1
            return
        if enc_row is None:
            self._insert_impl(key, vec)
            return
        self._insert_node(key, vec, enc_row, scale)

    def _restore_resharded(self, arrays: dict, meta: dict,
                           rec_shards: int) -> None:
        """Adopt a snapshot recorded at a different shard count: a
        deterministic REBUILD — live rows replay into fresh builders in
        canonical order (tombstoned rows do not survive; fresh builders
        draw fresh levels). Epoch and the sequence table are preserved so
        epoch-keyed consumers and ``keys()`` order are unaffected."""
        rows = self._canonical_rows(arrays, meta, rec_shards)
        # reset to empty in the CURRENT layout
        self._builder = None
        self._keys = []
        self._key2id = {}
        self._deleted = np.zeros(0, bool)
        self._enc = None
        self._scales = None
        self._device_graph = None
        self._deleted_dirty = False
        self._drop_derived()
        self._key2shard = {}
        self._seq = {}
        self._next_seq = 0
        if self.n_shards > 1:
            self._shards = [
                HNSW(distance_function=self.metric, M=self.M,
                     ef_construction=self.ef_construction,
                     ef_search=self.ef_search, seed=self.seed + j,
                     use_bulk_build=False, n_shards=1, dtype=self.dtype,
                     rerank_factor=self.rerank_factor,
                     beam_impl=self.beam_impl)
                for j in range(self.n_shards)]
        if (self.use_bulk_build and rows
                and all(r[3] is None for r in rows)):
            # bulk adoption fast path (DESIGN.md §13): a reshard is a
            # from-scratch rebuild over canonical fp32 rows, exactly the
            # shape the device-resident bulk ingest serves — each target
            # builder adopts one bulk-built graph instead of replaying
            # rows through per-row sequential inserts. Lossy codecs keep
            # the replay path: adopted rows must keep their recorded
            # encodings, which the builder-level bulk path re-derives.
            if self.n_shards == 1:
                self._adopt_bulk_graph([r[1] for r in rows],
                                       np.stack([r[2] for r in rows]),
                                       prenormalized=True)
            else:
                per: list[list[tuple]] = [[] for _ in range(self.n_shards)]
                for r in rows:
                    s = shard_of_key(r[1], self.n_shards)
                    per[s].append(r)
                    self._key2shard[r[1]] = s
                    self._seq[r[1]] = self._next_seq
                    self._next_seq += 1
                for s, child_rows in enumerate(per):
                    if child_rows:
                        self._shards[s]._adopt_bulk_graph(
                            [r[1] for r in child_rows],
                            np.stack([r[2] for r in child_rows]),
                            prenormalized=True)
        else:
            for _, key, vec, enc_row, scale in rows:
                self._insert_canonical(key, vec, enc_row, scale)
        if self.n_shards > 1:
            if rec_shards == 1:
                self._seq = {key: seq for seq, key, *_ in rows}
                self._next_seq = int(meta["n"])
            else:
                self._seq = {k: int(v) for k, v in meta["seq"]}
                self._next_seq = int(meta["next_seq"])
        self._epoch = int(meta["epoch"])

    export_index = VectorIndex.export
    exportIndex = VectorIndex.export
    load_index = VectorIndex.load
    loadIndex = VectorIndex.load
