"""VectorCodec layer (DESIGN.md §9): encode/decode bounds, fp32 parity,
int8/bf16 recall, encoded persistence (bit-for-bit restore, secure-delete
byte absence, cross-dtype rejection), serving transparency, and the
sharded codec paths (subprocess: the fan-out needs a multi-device mesh —
see tests/test_sharded.py for the pattern)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import make_index
from repro.core.codec import (CODEC_NAMES, effective_rerank, get_codec,
                              rerank_exact)
from repro.data.synthetic import make_corpus

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

BACKENDS = [("flat", {"dim": 32}),
            ("ivf", {"dim": 32, "nlist": 8, "nprobe": 8}),
            ("hnsw", {"M": 8, "ef_construction": 40, "ef_search": 32}),
            ("tiered", {"M": 8, "ef_construction": 40, "ef_search": 32})]


def mutate(idx, data, extra):
    """The shared CRUD sequence: every mutator the WAL knows."""
    idx.bulk_insert([f"d{i}" for i in range(len(data))], data)
    for j in range(3):
        idx.insert(f"x{j}", extra[j])
    idx.update("d5", extra[4])
    idx.delete("d7")
    idx.delete("x0")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# codec primitives
# ---------------------------------------------------------------------------
def test_roundtrip_error_bounds(rng):
    x = rng.normal(size=(64, 48)).astype(np.float32)
    # fp32: identity, no side arrays
    c = get_codec("fp32")
    enc, scales = c.encode(x)
    assert scales is None and enc.dtype == np.float32
    assert (c.decode(enc) == x).all()
    # bf16: 8-bit mantissa -> relative error <= 2^-8
    c = get_codec("bf16")
    enc, scales = c.encode(x)
    assert scales is None and enc.dtype.itemsize == 2
    err = np.abs(c.decode(enc) - x)
    assert (err <= np.abs(x) * 2.0 ** -8 + 1e-9).all()
    # int8: per-row scale -> abs error <= scale/2 = max|row|/254
    c = get_codec("int8")
    enc, scales = c.encode(x)
    assert enc.dtype == np.int8 and scales.shape == (64,)
    bound = np.max(np.abs(x), axis=1) / 254.0 + 1e-9
    assert (np.abs(c.decode(enc, scales) - x) <= bound[:, None]).all()
    # all-zero rows: scale 1.0, exact zeros back
    z = np.zeros((2, 8), np.float32)
    enc, scales = c.encode(z)
    assert (scales == 1.0).all() and (c.decode(enc, scales) == 0).all()


def test_codec_registry_and_sizes():
    assert set(CODEC_NAMES) == {"fp32", "bf16", "int8"}
    assert get_codec("fp32") is get_codec("FP32")       # shared instances
    assert get_codec("fp32").bytes_per_vector(128) == 512
    assert get_codec("bf16").bytes_per_vector(128) == 256
    assert get_codec("int8").bytes_per_vector(128) == 128 + 4
    with pytest.raises(ValueError, match="unknown storage dtype"):
        get_codec("fp16")
    # rerank policy: lossless never reranks; int8 over-fetches by default
    assert effective_rerank(get_codec("fp32"), 8) == 1
    assert effective_rerank(get_codec("int8"), None) == 4
    assert effective_rerank(get_codec("int8"), 2) == 2
    assert effective_rerank(get_codec("bf16"), None) == 1


def test_rerank_exact_contract(rng):
    vecs = rng.normal(size=(20, 8)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    q = rng.normal(size=(2, 8)).astype(np.float32)
    ids = np.array([[3, 7, 1, -1, 7], [0, -1, -1, -1, -1]])
    d, out = rerank_exact(vecs, q, ids, 3, metric="cosine")
    assert out.shape == (2, 3) and d.shape == (2, 3)
    assert set(out[0]) <= {1, 3, 7}                  # dups collapse
    assert list(out[1][1:]) == [-1, -1]              # short rows pad
    assert (np.diff(d[0]) >= 0).all()                # ascending


# ---------------------------------------------------------------------------
# fp32 parity + lossy recall
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,cfg", BACKENDS)
def test_fp32_codec_is_bitwise_default(kind, cfg, rng):
    """dtype='fp32' must be THE historical path — same results, same
    state bytes — on every backend (the pre-codec suite is the oracle
    for the default; this pins the explicit spelling to it)."""
    data = make_corpus(150, 32, seed=0)
    extra = make_corpus(8, 32, seed=1)
    q = make_corpus(4, 32, seed=2)
    a = make_index(kind, metric="cosine", **cfg)
    b = make_index(kind, metric="cosine", dtype="fp32", **cfg)
    mutate(a, data, extra)
    mutate(b, data, extra)
    ka, da = a.query_batch(q, 8)
    kb, db = b.query_batch(q, 8)
    assert ka == kb
    assert (np.asarray(da) == np.asarray(db)).all()
    aa, ma = a.state_dict()
    ab, mb = b.state_dict()
    assert set(aa) == set(ab)
    for name in aa:
        assert (np.asarray(aa[name]) == np.asarray(ab[name])).all(), name
    assert a.mutation_epoch == b.mutation_epoch


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind,cfg", BACKENDS)
def test_lossy_recall_vs_fp32(kind, cfg, dtype, rng):
    """Acceptance bar: recall@10 >= 0.95 vs the fp32 index on the
    synthetic corpus, for every backend."""
    data = make_corpus(600, 32, seed=3)
    q = make_corpus(8, 32, seed=4)
    keys = [f"d{i}" for i in range(len(data))]
    ref = make_index(kind, metric="cosine", **cfg)
    ref.bulk_insert(keys, data)
    rk, _ = ref.exact_query(q, 10)
    idx = make_index(kind, metric="cosine", dtype=dtype, **cfg)
    idx.bulk_insert(keys, data)
    fk, _ = idx.query_batch(q, 10)
    recall = (sum(len(set(a) & set(b)) for a, b in zip(rk, fk))
              / (len(q) * 10))
    assert recall >= 0.95, (kind, dtype, recall)


def test_int8_device_blocks_shrink(rng):
    data = make_corpus(500, 64, seed=5)
    keys = [f"d{i}" for i in range(500)]
    sizes = {}
    for dtype in ("fp32", "int8"):
        idx = make_index("flat", dim=64, metric="cosine", dtype=dtype)
        idx.bulk_insert(keys, data)
        idx.query(data[0], 1)            # force the pack
        sizes[dtype] = idx._rows.device_block_bytes()
    assert sizes["fp32"] / sizes["int8"] >= 3.5


def test_rerank_factor_config_roundtrips():
    idx = make_index("flat", dim=8, metric="cosine", dtype="int8",
                     rerank_factor=2)
    assert idx.config_dict()["rerank_factor"] == 2
    assert idx.config_dict()["dtype"] == "int8"
    assert idx.storage_dtype == "int8"
    assert make_index("flat", **idx.config_dict()).rerank_factor == 2


# ---------------------------------------------------------------------------
# encoded persistence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kind,cfg", BACKENDS)
def test_store_restore_bitforbit_per_codec(kind, cfg, dtype, tmp_path, rng):
    """snapshot + WAL-tail restore == the live index, byte for byte, for
    encoded state too (the encoded array is canonical — never re-derived
    — which is what makes this hold; DESIGN.md §9). fp32 is covered by
    tests/test_store.py."""
    from repro.store import IndexStore

    data = make_corpus(120, 32, seed=6)
    extra = make_corpus(8, 32, seed=7)
    q = make_corpus(4, 32, seed=8)
    idx = make_index(kind, store=IndexStore(str(tmp_path / "s")),
                     metric="cosine", dtype=dtype, **cfg)
    mutate(idx, data, extra)
    idx.query_batch(q, 5)                # pack / train derived state
    idx._store.snapshot(idx)
    idx.insert("late", extra[5])         # WAL tail past the snapshot
    idx.delete("d9")
    restored = make_index(kind, store=IndexStore(str(tmp_path / "s")))
    assert restored.storage_dtype == dtype
    a1, m1 = idx.state_dict()
    a2, m2 = restored.state_dict()
    assert m1 == m2
    assert set(a1) == set(a2)
    for name in a1:
        assert (np.asarray(a1[name]) == np.asarray(a2[name])).all(), name
    assert restored.mutation_epoch == idx.mutation_epoch
    k1, d1 = idx.query_batch(q, 5)
    k2, d2 = restored.query_batch(q, 5)
    assert k1 == k2
    assert (np.asarray(d1) == np.asarray(d2)).all()


def test_int8_snapshot_bytes_shrink(tmp_path, rng):
    from repro.store import IndexStore

    data = make_corpus(400, 64, seed=9)
    keys = [f"d{i}" for i in range(400)]
    sizes = {}
    for dtype in ("fp32", "int8"):
        root = tmp_path / dtype
        idx = make_index("flat", dim=64, metric="cosine", dtype=dtype,
                         store=IndexStore(str(root)))
        idx.bulk_insert(keys, data)
        idx._store.snapshot(idx)         # also truncates the WAL
        sizes[dtype] = sum(
            os.path.getsize(os.path.join(dp, fn))
            for dp, _, fns in os.walk(root) for fn in fns)
    assert sizes["fp32"] / sizes["int8"] >= 3.0


def test_secure_delete_erases_encoded_and_fp32_bytes(tmp_path, rng):
    """The §9 extension of the §7 byte-absence contract: after
    compaction, a deleted row's int8-encoded bytes AND its fp32 decode
    AND its raw WAL insert payload exist in no file under the store and
    in no host array."""
    from repro.store import IndexStore

    def dir_blob(root):
        blob = b""
        for dp, _, fns in os.walk(root):
            for fn in sorted(fns):
                with open(os.path.join(dp, fn), "rb") as f:
                    blob += f.read()
        return blob

    data = make_corpus(60, 32, seed=10)
    secret = (make_corpus(1, 32, seed=11)[0] * 7.7).astype(np.float32)
    idx = make_index("flat", dim=32, metric="cosine", dtype="int8",
                     store=IndexStore(str(tmp_path / "s")))
    idx.bulk_insert([f"d{i}" for i in range(60)], data)
    idx.insert("secret", secret)
    idx._store.snapshot(idx)
    row = idx._rows.key2row["secret"]
    enc_bytes = idx._rows.encoded[row].tobytes()
    fp32_bytes = idx._rows.vectors[row].tobytes()
    assert enc_bytes in dir_blob(tmp_path)     # sanity: durable pre-delete
    idx.delete("secret")
    blob = dir_blob(tmp_path)                  # tombstoned, NOT yet erased
    assert enc_bytes in blob
    idx._store.compact(idx)
    blob = dir_blob(tmp_path)
    assert enc_bytes not in blob
    assert fp32_bytes not in blob
    assert secret.tobytes() not in blob        # the WAL insert payload
    assert enc_bytes not in idx._rows.encoded.tobytes()
    assert fp32_bytes not in idx._rows.vectors.tobytes()
    assert "secret" not in blob.decode("latin1")


def test_cross_dtype_restore_rejection(tmp_path, rng):
    from repro.store import IndexStore

    data = make_corpus(40, 16, seed=12)
    idx = make_index("flat", dim=16, metric="cosine", dtype="int8",
                     store=IndexStore(str(tmp_path / "s")))
    idx.bulk_insert([f"d{i}" for i in range(40)], data)
    idx._store.snapshot(idx)
    with pytest.raises(ValueError, match="cannot restore.*transcoded"):
        make_index("flat", store=IndexStore(str(tmp_path / "s")),
                   dim=16, dtype="fp32")
    with pytest.raises(ValueError, match="cannot restore.*transcoded"):
        make_index("flat", store=IndexStore(str(tmp_path / "s")),
                   dim=16, dtype="bf16")
    # omitting dtype keeps the stored codec
    restored = make_index("flat", store=IndexStore(str(tmp_path / "s")),
                          dim=16)
    assert restored.storage_dtype == "int8"
    # a mismatched restore_state (e.g. hand-fed arrays) also fails loudly
    arrays, meta = idx.state_dict()
    fresh = make_index("flat", dim=16, metric="cosine", dtype="fp32")
    with pytest.raises(ValueError, match="encoded rows"):
        fresh.restore_state(arrays, meta)


# ---------------------------------------------------------------------------
# serving + tiers stay codec-transparent
# ---------------------------------------------------------------------------
def test_engine_epoch_invalidation_is_codec_transparent(rng):
    from repro.serve.retrieval import RetrievalEngine

    data = make_corpus(80, 16, seed=13)
    idx = make_index("flat", dim=16, metric="cosine", dtype="int8")
    idx.bulk_insert([f"d{i}" for i in range(80)], data)
    eng = RetrievalEngine(idx, max_batch=8, cache_size=64)
    assert eng.index_dtype == "int8"
    q = data[3]
    r1 = eng.retrieve_one(q, k=5)
    r2 = eng.retrieve_one(q, k=5)
    assert r2.from_cache and r2.keys == r1.keys
    victim = r1.keys[0]
    idx.delete(victim)                       # privacy op bumps the epoch
    r3 = eng.retrieve_one(q, k=5)
    assert not r3.from_cache
    assert victim not in r3.keys
    assert eng.stats.invalidations == 1


def test_tiered_slow_tier_is_encoded(rng):
    from repro.core.tiered import auto_prefetch_p

    data = make_corpus(200, 32, seed=14)
    keys = [f"d{i}" for i in range(200)]
    stores = {}
    for dtype in ("fp32", "int8"):
        idx = make_index("tiered", metric="cosine", M=8,
                         ef_construction=40, cache_rows=64, dtype=dtype)
        idx.bulk_insert(keys, data)
        idx.query(data[0], 5)
        g, store = idx._tiers()
        stores[dtype] = store
        assert idx.stats.transactions > 0    # accounting still runs
    assert (stores["fp32"].slow_tier_bytes
            / stores["int8"].slow_tier_bytes) >= 3.5
    # bytes-budgeted prefetch: an int8 slow tier prefetches ~4x more
    # rows per transaction (the paper's bytes-per-transaction economics)
    assert stores["int8"].p == auto_prefetch_p(32, 1)
    assert stores["int8"].p == 4 * stores["fp32"].p


def test_hnsw_incremental_sync_matches_full_rebuild_int8(rng):
    """Mutating after the first query drives the codec variant of the
    dirty-row scatter; its resident graph must equal a from-scratch
    conversion of the same host state."""
    data = make_corpus(100, 16, seed=15)
    idx = make_index("hnsw", metric="cosine", M=8, ef_construction=40,
                     dtype="int8")
    idx.bulk_insert([f"d{i}" for i in range(100)], data)
    q = make_corpus(3, 16, seed=16)
    idx.query_batch(q, 5)                    # resident device graph
    idx.insert("new", make_corpus(1, 16, seed=17)[0])
    idx.delete("d3")
    k_inc, d_inc = idx.query_batch(q, 5)     # incremental scatter path
    dg = idx._device_graph
    idx._device_graph = None                 # force the full conversion
    k_full, d_full = idx.query_batch(q, 5)
    assert k_inc == k_full
    assert (np.asarray(d_inc) == np.asarray(d_full)).all()
    assert (np.asarray(dg.vectors) ==
            np.asarray(idx._device_graph.vectors)).all()
    assert (np.asarray(dg.scales) ==
            np.asarray(idx._device_graph.scales)).all()


# ---------------------------------------------------------------------------
# flat under a lossy codec: the scan's own top-k, with no host re-score
# ---------------------------------------------------------------------------
TESTS = os.path.dirname(os.path.abspath(__file__))


def counted_reranks(rows) -> list[int]:
    """Wrap ``rows.rerank_topk`` -> the candidate width of each call."""
    calls, orig = [], rows.rerank_topk

    def counted(queries, gids, k):
        calls.append(np.asarray(gids).shape[1])
        return orig(queries, gids, k)

    rows.rerank_topk = counted
    return calls


def flat_scan_parity(metric: str, n_shards: int) -> None:
    """int8 flat ``query_batch`` against the composition it replaced,
    ``rerank_exact`` over the scan's k·4 candidates: row by row the same
    key set, distances within 1e-6 of their scale, ascending, and no
    rerank call."""
    data = make_corpus(300, 32, seed=0)
    extra = make_corpus(8, 32, seed=1)
    q = make_corpus(12, 32, seed=2)
    idx = make_index("flat", metric=metric, dim=32, n_shards=n_shards,
                     dtype="int8")
    mutate(idx, data, extra)
    calls = counted_reranks(idx._rows)
    keys, d = idx.query_batch(q, 10)
    assert calls == []
    _, cand = idx._rows.topk(q, 40)
    ref_d, ref_rows = rerank_exact(idx._rows.vectors, q, cand, 10,
                                   metric=metric)
    for row, ref in zip(keys, ref_rows):
        assert set(row) == {idx._rows.key_of_row(int(r)) for r in ref}
    d = np.asarray(d)
    assert (np.abs(d - ref_d) <= 1e-6 * np.maximum(1.0, np.abs(ref_d))).all()
    assert (np.diff(d, axis=1) >= 0).all()


@pytest.mark.parametrize("n_shards", [1, 8])
@pytest.mark.parametrize("metric", ["cosine", "ip", "l2"])
def test_flat_lossy_scan_is_the_exact_topk(metric, n_shards):
    if n_shards == 1:
        flat_scan_parity(metric, n_shards)
        return
    run_sub(f"""
        import sys
        sys.path.insert(0, {TESTS!r})
        from test_codec import flat_scan_parity
        flat_scan_parity({metric!r}, {n_shards})
    """)


def test_flat_bf16_wire_keeps_the_rerank():
    """S > 1 over the bf16 wire orders by bf16-rounded distances: only
    there does flat int8 still over-fetch k·4 and rerank, and the rerank
    gives back the fp32 wire's keys and float32 distances."""
    from repro.core.sharded import ShardedRows

    assert ShardedRows(n_shards=1, wire_bf16=True).exact_distances
    assert ShardedRows(n_shards=8, wire_bf16=False).exact_distances
    assert not ShardedRows(n_shards=8, wire_bf16=True).exact_distances
    run_sub(f"""
        import sys
        import numpy as np
        sys.path.insert(0, {TESTS!r})
        from test_codec import counted_reranks, mutate
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        idx = make_index("flat", metric="cosine", dim=32, n_shards=8,
                         dtype="int8")
        mutate(idx, make_corpus(300, 32, seed=0), make_corpus(8, 32, seed=1))
        q = make_corpus(12, 32, seed=2)
        calls = counted_reranks(idx._rows)
        k32, d32 = idx.query_batch(q, 10)
        assert calls == []
        idx._rows.wire_bf16 = True
        kbf, dbf = idx.query_batch(q, 10)
        assert calls == [40], calls
        assert [set(r) for r in kbf] == [set(r) for r in k32]
        np.testing.assert_allclose(dbf, d32, rtol=0, atol=1e-6)
    """)


@pytest.mark.parametrize("kind,widths", [("hnsw", [20]), ("ivf", [20]),
                                         ("pool", [])])
def test_which_lossy_backends_rerank(kind, widths, monkeypatch):
    """HNSW and IVF over-fetch under int8 and re-score on the host, once
    per ``query_batch``. The tenant pool is flat search over a tenant's
    slabs: like flat, it returns its scan's own float32 top-k, from
    ``query_batch`` and from the cross-tenant ``query_batch_multi``."""
    import repro.core.interface as interface
    import repro.core.sharded as sharded
    from repro.core.tenancy import IndexPool

    calls = []

    def counted(vectors, queries, ids, k, *, metric):
        calls.append(np.asarray(ids).shape[1])
        return rerank_exact(vectors, queries, ids, k, metric=metric)

    monkeypatch.setattr(interface, "rerank_exact", counted)
    monkeypatch.setattr(sharded, "rerank_exact", counted)
    data = make_corpus(200, 32, seed=0)
    keys = [f"d{i}" for i in range(200)]
    q = make_corpus(4, 32, seed=2)
    if kind == "pool":
        pool = IndexPool(dim=32, dtype="int8")
        pool.bulk_insert("a", keys[:100], data[:100])
        pool.bulk_insert("b", keys[100:], data[100:])
        out, _ = pool.query_batch("a", q, 5)
        multi, _ = pool.query_batch_multi(q, ["a", "b", "a", "b"], 5)
        assert all(len(row) == 5 for row in multi)
    else:
        cfg = dict(BACKENDS)[kind]
        idx = make_index(kind, metric="cosine", dtype="int8", **cfg)
        idx.bulk_insert(keys, data)
        out, _ = idx.query_batch(q, 5)
    assert calls == widths
    assert all(len(row) == 5 for row in out)


# ---------------------------------------------------------------------------
# sharded codec paths (multi-device mesh via subprocess)
# ---------------------------------------------------------------------------
def test_sharded_codec_parity_bitforbit():
    """8-shard vs 1-shard int8/bf16 flat+ivf: same keys, same distances
    (the rerank re-scores both against the same canonical host rows),
    BIT-identical state_dict — and the hnsw exact phase stays
    shard-count independent under int8."""
    run_sub("""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(300, 32, seed=0)
        extra = make_corpus(8, 32, seed=1)
        q = make_corpus(6, 32, seed=2)
        def mutate(idx):
            idx.bulk_insert([f"d{i}" for i in range(len(data))], data)
            for j in range(4):
                idx.insert(f"x{j}", extra[j])
            idx.update("d5", extra[4])
            idx.delete("d7"); idx.delete("x0")
        for dt in ("int8", "bf16"):
            for kind, cfg in (("flat", {"dim": 32}),
                              ("ivf", {"dim": 32, "nlist": 16,
                                       "nprobe": 4})):
                i1 = make_index(kind, metric="cosine", n_shards=1,
                                dtype=dt, **cfg)
                i8 = make_index(kind, metric="cosine", n_shards=8,
                                dtype=dt, **cfg)
                mutate(i1); mutate(i8)
                k1, d1 = i1.query_batch(q, 10)
                k8, d8 = i8.query_batch(q, 10)
                assert k1 == k8, (kind, dt)
                np.testing.assert_allclose(np.asarray(d1), np.asarray(d8),
                                           rtol=1e-6, atol=0)
                a1, m1 = i1.state_dict(); a8, m8 = i8.state_dict()
                assert m1 == m8 and set(a1) == set(a8)
                for name in a1:
                    assert (np.asarray(a1[name])
                            == np.asarray(a8[name])).all(), (kind, dt, name)
                assert i1.mutation_epoch == i8.mutation_epoch
        h1 = make_index("hnsw", metric="cosine", M=8, ef_construction=40,
                        n_shards=1, dtype="int8")
        h8 = make_index("hnsw", metric="cosine", M=8, ef_construction=40,
                        n_shards=8, dtype="int8")
        mutate(h1); mutate(h8)
        ek1, ed1 = h1.exact_query(q, 10)
        ek8, ed8 = h8.exact_query(q, 10)
        assert ek1 == ek8
        np.testing.assert_allclose(np.asarray(ed1), np.asarray(ed8),
                                   rtol=1e-5, atol=1e-6)
        print("OK")
    """)


def test_sharded_codec_store_and_secure_delete():
    """int8 8-shard: warm restore bit-for-bit, 8->1 reshard-on-restore,
    and the secure-delete byte-absence of encoded bytes, sharded."""
    run_sub("""
        import numpy as np, os, tempfile
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        from repro.store import IndexStore
        def dir_blob(root):
            blob = b""
            for dp, _, fns in os.walk(root):
                for fn in sorted(fns):
                    blob += open(os.path.join(dp, fn), "rb").read()
            return blob
        data = make_corpus(200, 32, seed=0)
        q = make_corpus(4, 32, seed=2)
        root = tempfile.mkdtemp()
        idx = make_index("flat", dim=32, metric="cosine", n_shards=8,
                         dtype="int8", store=IndexStore(os.path.join(root, "s")))
        idx.bulk_insert([f"d{i}" for i in range(200)], data)
        secret = (make_corpus(1, 32, seed=9)[0] * 7.7).astype(np.float32)
        idx.insert("secret", secret)
        idx._store.snapshot(idx)
        row = idx._rows.key2row["secret"]
        enc_bytes = idx._rows.encoded[row].tobytes()
        # same-shard warm restore: bit-for-bit
        r8 = make_index("flat", store=IndexStore(os.path.join(root, "s")))
        assert r8.shard_count == 8 and r8.storage_dtype == "int8"
        a1, m1 = idx.state_dict(); a2, m2 = r8.state_dict()
        assert m1 == m2
        for name in a1:
            assert (np.asarray(a1[name]) == np.asarray(a2[name])).all()
        # reshard on restore: 8 -> 1, same results
        r1 = make_index("flat", store=IndexStore(os.path.join(root, "s")),
                        n_shards=1)
        k8, d8 = r8.query_batch(q, 5)
        k1, d1 = r1.query_batch(q, 5)
        assert k8 == k1
        np.testing.assert_allclose(np.asarray(d8), np.asarray(d1),
                                   rtol=1e-6, atol=0)
        # sharded secure delete: encoded + fp32 bytes physically gone
        fp32_bytes = idx._rows.vectors[row].tobytes()
        idx.delete("secret")
        idx._store.compact(idx)
        blob = dir_blob(root)
        assert enc_bytes not in blob and fp32_bytes not in blob
        assert secret.tobytes() not in blob
        # hnsw int8 reshard keeps the CANONICAL encodings: the replay
        # adopts each recorded row's encoded bytes + scale instead of
        # re-quantizing (re-encode is not ulp-stable) — graphs are
        # rebuilt, row payloads are the original bytes
        h8 = make_index("hnsw", metric="cosine", M=8, ef_construction=40,
                        n_shards=8, dtype="int8",
                        store=IndexStore(os.path.join(root, "h")))
        h8.bulk_insert([f"d{i}" for i in range(120)], data[:120])
        h8._store.snapshot(h8)
        h1 = make_index("hnsw", store=IndexStore(os.path.join(root, "h")),
                        n_shards=1)
        orig = {}
        for child in h8._shards:
            for key, node in child._key2id.items():
                orig[key] = (child._enc[node].tobytes(),
                             child._scales[node])
        for key, node in h1._key2id.items():
            assert h1._enc[node].tobytes() == orig[key][0], key
            assert h1._scales[node] == orig[key][1], key
        print("OK")
    """)
