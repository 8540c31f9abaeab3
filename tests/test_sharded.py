"""Mesh-sharded VectorIndex conformance (DESIGN.md §8): shard parity,
sharded durability (reshard-on-restore + secure delete), and the serving
layer's epoch invalidation under shard-routed mutations.

Sharded paths need a multi-device mesh, so every test spawns a
subprocess that sets the fake-device XLA flag BEFORE importing jax (the
main pytest process must keep 1 CPU device — see conftest.py). Each
subprocess builds BOTH the 8-shard and the 1-shard index and compares.

Parity contract asserted here (and what it deliberately does not say):
  * flat / ivf — fully sharded: ``query_batch`` returns the same keys in
    the same order at any shard count, distances to <= 1 ulp (the CPU
    dot kernel may differ in summation order at tiny batch shapes), and
    ``state_dict`` is BIT-identical (canonical arrays, derived
    placement);
  * hnsw / tiered — per-shard graphs (a navigable small-world graph
    cannot be row-partitioned without changing results): the exact/flat
    phase is shard-count independent, the canonical key set / order /
    epoch match, and ANN recall vs the exact oracle holds at both shard
    counts. The per-shard graphs themselves legitimately differ.
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, prelude: str = "") -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# the shared CRUD sequence: bulk insert + singles + updates + deletes,
# exercising every mutator the WAL knows
MUTATE = """
def mutate(idx, data, extra):
    idx.bulk_insert([f"d{i}" for i in range(len(data))], data)
    for j in range(4):
        idx.insert(f"x{j}", extra[j])
    idx.update("d5", extra[4])
    idx.update("x1", extra[5])
    idx.delete("d7"); idx.delete("x0"); idx.delete("d63")
"""


def test_flat_ivf_shard_parity_bitforbit():
    """8-shard vs 1-shard after the same mutation sequence: same keys,
    <=1-ulp distances, BIT-identical state_dict (epoch included)."""
    out = run_sub(prelude=MUTATE, code="""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(300, 32, seed=0)
        extra = make_corpus(8, 32, seed=1)
        q = make_corpus(6, 32, seed=2)
        for kind, cfg in (("flat", {}), ("ivf", {"nlist": 16, "nprobe": 4})):
            i1 = make_index(kind, dim=32, metric="cosine", n_shards=1, **cfg)
            i8 = make_index(kind, dim=32, metric="cosine", n_shards=8, **cfg)
            mutate(i1, data, extra); mutate(i8, data, extra)
            k1, d1 = i1.query_batch(q, 10)
            k8, d8 = i8.query_batch(q, 10)
            assert k1 == k8, (kind, "keys diverge")
            np.testing.assert_allclose(np.asarray(d1), np.asarray(d8),
                                       rtol=1e-6, atol=0)
            # exact phase: nprobe=nlist / full scan, same contract
            ek1, ed1 = i1.exact_query(q, 12)
            ek8, ed8 = i8.exact_query(q, 12)
            assert ek1 == ek8
            np.testing.assert_allclose(np.asarray(ed1), np.asarray(ed8),
                                       rtol=1e-6, atol=1e-7)
            # k > live: None-padding identical
            kk1, _ = i1.query_batch(q[:1], 400)
            kk8, _ = i8.query_batch(q[:1], 400)
            assert kk1 == kk8
            # canonical state: BIT-identical at any shard count
            a1, m1 = i1.state_dict(); a8, m8 = i8.state_dict()
            assert m1 == m8, (kind, "meta diverges")
            assert set(a1) == set(a8)
            for name in a1:
                assert a1[name].dtype == a8[name].dtype
                assert a1[name].tobytes() == a8[name].tobytes(), (kind, name)
            assert i1.mutation_epoch == i8.mutation_epoch
        print("OK")
    """)
    assert "OK" in out


def test_hnsw_tiered_shard_parity():
    """Per-shard-graph backends: exact phase + canonical key set / order /
    epoch are shard-count independent; ANN recall holds at both counts."""
    out = run_sub(prelude=MUTATE, code="""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(200, 16, seed=0)
        extra = make_corpus(8, 16, seed=1)
        q = make_corpus(5, 16, seed=2)
        for kind in ("hnsw", "tiered"):
            i1 = make_index(kind, metric="cosine", M=8, ef_construction=60,
                            ef_search=48, n_shards=1)
            i8 = make_index(kind, metric="cosine", M=8, ef_construction=60,
                            ef_search=48, n_shards=8)
            mutate(i1, data, extra); mutate(i8, data, extra)
            assert i1.size == i8.size == 201
            assert i1.keys() == i8.keys()          # canonical order (seq)
            assert i1.mutation_epoch == i8.mutation_epoch
            ek1, ed1 = i1.exact_query(q, 10)
            ek8, ed8 = i8.exact_query(q, 10)
            assert ek1 == ek8, (kind, "exact phase diverges across shards")
            np.testing.assert_allclose(np.asarray(ed1), np.asarray(ed8),
                                       rtol=1e-6, atol=1e-7)
            for idx in (i1, i8):
                hits = tot = 0
                kq, _ = idx.query_batch(q, 5)
                for b in range(len(q)):
                    ex, _ = idx.exact_query(q[b], 5)
                    hits += len({x for x in kq[b] if x} & set(ex))
                    tot += 5
                assert hits / tot >= 0.8, (kind, idx.shard_count, hits / tot)
            # deleted keys are gone from every shard's results
            kq, _ = i8.query_batch(data[7][None], 10)
            assert "d7" not in kq[0]
            # epoch parity survives compact() too (empty shards must not
            # add spurious bumps; the outer delta is one per live row)
            i1.compact(); i8.compact()
            assert i1.mutation_epoch == i8.mutation_epoch
            assert i1.keys() == i8.keys()
        print("OK")
    """)
    assert "OK" in out


def test_sharded_state_roundtrip_same_count():
    """S=8 state_dict -> restore_state on a fresh S=8 instance reproduces
    queries exactly (per-shard graphs ride the namespaced sub-states)."""
    out = run_sub(prelude=MUTATE, code="""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(150, 16, seed=0)
        extra = make_corpus(8, 16, seed=1)
        q = make_corpus(4, 16, seed=2)
        for kind in ("flat", "ivf", "hnsw", "tiered"):
            idx = make_index(kind, dim=16, metric="cosine", M=8,
                             ef_construction=60, n_shards=8)
            mutate(idx, data, extra)
            idx.query_batch(q, 5)                  # train/pack derived state
            a, m = idx.state_dict()
            idx2 = make_index(kind, dim=16, metric="cosine", M=8,
                              ef_construction=60, n_shards=8)
            idx2.restore_state(a, m)
            k1, d1 = idx.query_batch(q, 5)
            k2, d2 = idx2.query_batch(q, 5)
            assert k1 == k2, kind
            np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
            assert idx2.mutation_epoch == idx.mutation_epoch
            assert idx2.keys() == idx.keys()
        print("OK")
    """)
    assert "OK" in out


def test_sharded_durability_reshard_restore():
    """Snapshot at 8 shards -> restore at 1, and 1 -> 8 (store-level,
    snapshot + WAL replay): query parity across the reshard."""
    out = run_sub(prelude=MUTATE, code="""
        import numpy as np, tempfile, os
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        from repro.store import IndexStore
        data = make_corpus(120, 16, seed=0)
        extra = make_corpus(8, 16, seed=1)
        q = make_corpus(4, 16, seed=2)
        for kind in ("flat", "ivf", "hnsw"):
            with tempfile.TemporaryDirectory() as td:
                s8 = IndexStore(os.path.join(td, "s8"))
                i8 = make_index(kind, dim=16, metric="cosine", M=8,
                                ef_construction=60, n_shards=8, store=s8)
                mutate(i8, data, extra)
                i8.query_batch(q, 5)               # IVF trains centroids
                s8.snapshot(i8)
                i8.insert("late", extra[6])        # rides the WAL only
                # 8 -> 1: explicit override reshards on restore
                r1 = make_index(kind, dim=16, metric="cosine", M=8,
                                ef_construction=60, n_shards=1,
                                store=IndexStore(os.path.join(td, "s8")))
                assert r1.shard_count == 1
                assert r1.size == i8.size and "late" in r1
                assert r1.mutation_epoch == i8.mutation_epoch
                assert r1.keys() == i8.keys()
                ek8, ed8 = i8.exact_query(q, 8)
                ek1, ed1 = r1.exact_query(q, 8)
                assert ek8 == ek1, kind
                np.testing.assert_allclose(np.asarray(ed8), np.asarray(ed1),
                                           rtol=1e-6, atol=1e-7)
                if kind in ("flat", "ivf"):        # fully sharded: ANN too
                    k8, _ = i8.query_batch(q, 5)
                    k1, _ = r1.query_batch(q, 5)
                    assert k8 == k1
            with tempfile.TemporaryDirectory() as td:
                s1 = IndexStore(os.path.join(td, "s1"))
                i1 = make_index(kind, dim=16, metric="cosine", M=8,
                                ef_construction=60, n_shards=1, store=s1)
                mutate(i1, data, extra)
                i1.query_batch(q, 5)
                s1.snapshot(i1)
                # 1 -> 8
                r8 = make_index(kind, dim=16, metric="cosine", M=8,
                                ef_construction=60, n_shards=8,
                                store=IndexStore(os.path.join(td, "s1")))
                assert r8.shard_count == 8
                assert r8.size == i1.size
                assert r8.keys() == i1.keys()
                ek1, _ = i1.exact_query(q, 8)
                ek8, _ = r8.exact_query(q, 8)
                assert ek1 == ek8, kind
        # bulk-build epoch parity across the reshard: the 1-shard
        # use_bulk_build path bumps ONCE per batch, so WAL replay at a
        # different shard count must see the same per-record epoch deltas
        # — or the delete record after the bulk would be skipped as stale
        # and the retracted doc would resurrect
        with tempfile.TemporaryDirectory() as td:
            s1 = IndexStore(os.path.join(td, "bb"))
            i1 = make_index("hnsw", metric="cosine", M=8, ef_construction=60,
                            use_bulk_build=True, n_shards=1, store=s1)
            i1.bulk_insert([f"d{i}" for i in range(120)], data)
            i1.delete("d7")                    # WAL: bulk@0, delete@1
            r8 = make_index("hnsw", metric="cosine", M=8, ef_construction=60,
                            use_bulk_build=True, n_shards=8,
                            store=IndexStore(os.path.join(td, "bb")))
            assert r8.size == 119 and "d7" not in r8
            assert r8.mutation_epoch == i1.mutation_epoch == 2
        print("OK")
    """)
    assert "OK" in out


def test_sharded_secure_delete_compaction():
    """Secure-delete contract on a SHARDED index: after store.compact(),
    a deleted vector's bytes (and its key) appear in no file under the
    store — no per-shard page, no WAL, no manifest."""
    out = run_sub("""
        import numpy as np, tempfile, os
        from repro.core import make_index
        from repro.store import IndexStore
        rng = np.random.default_rng(0)
        data = rng.normal(size=(60, 16)).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            store = IndexStore(td)
            idx = make_index("flat", dim=16, metric="cosine", n_shards=8,
                             store=store)
            idx.bulk_insert([f"doc-{i}" for i in range(60)], data)
            secret = np.asarray(idx.state_dict()[0]["vectors"][13],
                                np.float32).tobytes()
            idx.delete("doc-13")
            store.compact(idx)
            idx.query_batch(data[:2], 5)           # still serves after compact
            hits = []
            for root, _, files in os.walk(td):
                for f in files:
                    blob = open(os.path.join(root, f), "rb").read()
                    if secret in blob or b"doc-13" in blob:
                        hits.append(os.path.join(root, f))
            assert not hits, hits
            # live neighbours survived, in every shard
            k, _ = idx.query_batch(data[14][None], 3)
            assert k[0][0] == "doc-14"
            assert sum(s["live"] for s in idx.shard_stats()) == 59
        print("OK")
    """)
    assert "OK" in out


def test_engine_epoch_invalidation_under_shard_routed_mutations():
    """RetrievalEngine over a sharded index: a delete that lands on ONE
    shard still invalidates the whole LRU (global epoch), so a retracted
    key is never served from cache (DESIGN.md §6/§8)."""
    out = run_sub("""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        from repro.serve.retrieval import RetrievalEngine
        data = make_corpus(100, 16, seed=0)
        idx = make_index("flat", dim=16, metric="cosine", n_shards=8)
        idx.bulk_insert([f"d{i}" for i in range(100)], data)
        eng = RetrievalEngine(idx, max_batch=16)
        assert eng.shards == 8
        q = data[7]
        r1 = eng.retrieve_one(q, k=3)
        assert r1.keys[0] == "d7" and not r1.from_cache
        r2 = eng.retrieve_one(q, k=3)
        assert r2.from_cache and eng.stats.cache_hits == 1
        idx.delete("d7")                           # routes to one shard...
        r3 = eng.retrieve_one(q, k=3)              # ...but flushes the LRU
        assert not r3.from_cache
        assert "d7" not in r3.keys
        assert eng.stats.invalidations == 1
        print("OK")
    """)
    assert "OK" in out


def test_shard_sweep_latency_and_capacity():
    """The bench_shard acceptance shape in miniature: per-shard work
    (rows per device) drops as 1/S while the key->shard routing keeps
    shards balanced; results stay exact at every S."""
    out = run_sub("""
        import numpy as np
        from repro.core import make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(4000, 16, seed=0)
        keys = [f"d{i}" for i in range(4000)]
        q = make_corpus(4, 16, seed=1)
        ref = None
        for s in (1, 2, 4, 8):
            idx = make_index("flat", dim=16, metric="cosine", n_shards=s)
            idx.bulk_insert(keys, data)
            k, _ = idx.query_batch(q, 10)
            if ref is None:
                ref = k
            assert k == ref, s                     # exact at every S
            stats = idx.shard_stats()
            assert len(stats) == s
            live = [st["live"] for st in stats]
            assert sum(live) == 4000
            assert max(live) <= (4000 // s) * 1.2  # hash keeps it balanced
        print("OK")
    """)
    assert "OK" in out


def test_stacked_fanout_matches_loop_bitwise():
    """The one-dispatch stacked fan-out (core/stacked.py) against the
    per-child Python loop it replaced, at S in {2, 3, 8} on both
    graph-backed kinds: same keys in the same order (the loop's stable
    shard-major tie order equals the stacked merge's two-key gid
    order), distances to <= 1 ulp (the capacity-padded stacked dot may
    differ from the per-child shape in summation order — the same
    allowance the flat/ivf parity contract documents above), and
    EXACTLY one device dispatch per ``query_batch`` regardless of
    shard count — the ISSUE 6 acceptance assert."""
    out = run_sub("""
        import numpy as np
        from repro.core import dispatch, make_index
        from repro.data.synthetic import make_corpus
        data = make_corpus(250, 16, seed=0)
        keys = [f"d{i}" for i in range(250)]
        q = make_corpus(6, 16, seed=2)
        for kind in ("hnsw", "tiered"):
            for s in (2, 3, 8):
                idx = make_index(kind, metric="cosine", M=8,
                                 ef_construction=60, ef_search=48,
                                 n_shards=s)
                idx.bulk_insert(keys, data)
                idx.delete("d11")        # tombstones flow into the stack
                before = dispatch.get("stacked.search_stacked")
                kq, dq = idx.query_batch(q, 5)
                assert dispatch.get("stacked.search_stacked") == before + 1, \
                    (kind, s)
                kl, dl = idx._query_batch_sharded_loop(q, 5, 48)
                assert kq == kl, (kind, s)
                np.testing.assert_allclose(np.asarray(dq),
                                           np.asarray(dl),
                                           rtol=0, atol=2.5e-7)
                assert all("d11" not in row for row in kq)
                # warm path: still exactly one dispatch, nothing rebuilt
                before = dispatch.get("stacked.search_stacked")
                idx.query_batch(q, 5)
                assert dispatch.get("stacked.search_stacked") == before + 1, \
                    (kind, s)
        print("OK")
    """)
    assert "OK" in out


def test_exact_block_cache_invalidation():
    """Epoch-keyed exact-phase blocks: built once, reused with ZERO
    per-query block uploads on the steady state, and invalidated by
    every mutation class (delete / insert / compact) — a stale cache
    must never serve a retracted row. Also pins the compiled-fn cache:
    churning epochs must not grow ``_fanout_topk_fn``'s lru_cache."""
    out = run_sub("""
        import numpy as np
        from repro.core import make_index, sharded
        from repro.data.synthetic import make_corpus
        data = make_corpus(120, 16, seed=0)
        idx = make_index("hnsw", metric="cosine", M=8, ef_construction=60,
                         ef_search=48, n_shards=4)
        idx.bulk_insert([f"d{i}" for i in range(120)], data)
        q = data[7][None] + 0.001
        p0 = sharded.PLACE_COUNT
        ek, _ = idx.exact_query(q, 5)
        assert ek[0][0] == "d7"
        assert sharded.PLACE_COUNT == p0 + 1       # one build, one upload
        for _ in range(5):                          # steady state...
            idx.exact_query(q, 5)
            idx.query_batch(q, 5)
        assert sharded.PLACE_COUNT == p0 + 1        # ...zero re-uploads
        idx.delete("d7")
        ek2, _ = idx.exact_query(q, 5)
        assert "d7" not in ek2[0], "stale block cache served retracted row"
        assert sharded.PLACE_COUNT == p0 + 2        # delete rebuilt blocks
        idx.insert("z0", data[7])
        ek3, _ = idx.exact_query(q, 5)
        assert ek3[0][0] == "z0"                    # insert visible at once
        idx.compact()
        ek4, _ = idx.exact_query(q, 5)
        assert ek4[0][0] == "z0" and "d7" not in ek4[0]
        info = sharded._fanout_topk_fn.cache_info()
        assert info.currsize <= 8, info             # no churn across epochs
        print("OK")
    """)
    assert "OK" in out


def test_quantize_slack_bounded():
    """No shard over-fetches past k ("slack" is zero): free slots, more
    than one 1024-row kernel tile of them in a shard, are masked inside
    the compiled scan (REPRO_PALLAS=interpret runs the Pallas kernel).
    The sharded flat index and the tenant slab search (S=1 and S=2 pools)
    return the answers of an S=1 flat index, and the compiled fan-out is
    reused across pack states with different dead-slot counts."""
    out = run_sub("""
        import os
        os.environ["REPRO_PALLAS"] = "interpret"
        import numpy as np
        from repro.core import IndexPool, make_index, sharded
        from repro.core.sharded import shard_of_key
        from repro.data.synthetic import make_corpus
        S, K = 2, 10
        pool_keys = [f"d{i}" for i in range(8000)]
        k0 = [k for k in pool_keys if shard_of_key(k, S) == 0][:1400]
        k1 = [k for k in pool_keys if shard_of_key(k, S) == 1][:100]
        more = [k for k in pool_keys if shard_of_key(k, S) == 1][100:300]
        # rows in the positive orthant; queries near them, and queries in
        # the negative orthant, whose every live cosine distance is above
        # a free slot's zero row (1.0): an unmasked free slot would
        # displace a live answer
        data = np.abs(make_corpus(1700, 16, seed=0))
        q = np.abs(make_corpus(8, 16, seed=2))
        q[4:] *= -1

        def same(a, b, tag):
            ka, da = a
            kb, db = b
            assert ka == kb, tag
            np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                                       rtol=1e-6, atol=1e-7, err_msg=tag)

        # sharded flat: shard 1 holds 100 rows in a block of >= 1400
        i1 = make_index("flat", dim=16, metric="cosine", n_shards=1)
        iS = make_index("flat", dim=16, metric="cosine", n_shards=S)
        for idx in (i1, iS):
            idx.bulk_insert(k0 + k1, data[:1500])
        same(i1.query_batch(q, K), iS.query_batch(q, K), "initial")
        fn = sharded._fanout_topk_fn
        size0 = fn.cache_info().currsize
        steps = (("delete 200 in shard 0", lambda i: [i.delete(x) for x in k0[:200]]),
                 ("insert 200 in shard 1", lambda i: i.bulk_insert(more, data[1500:])),
                 ("delete 1100 in shard 0", lambda i: [i.delete(x) for x in k0[200:1300]]))
        for tag, step in steps:
            step(i1)
            step(iS)
            same(i1.query_batch(q, K), iS.query_batch(q, K), tag)
        assert fn.cache_info().currsize == size0, fn.cache_info()

        # tenant slab search: 2048-row slabs, most of each slot free
        for n_shards in (1, S):
            pool = IndexPool(dim=16, slab_rows=2048, n_shards=n_shards)
            orc = make_index("flat", dim=16, metric="cosine")
            for x in (pool, orc):
                args = ("a",) if x is pool else ()
                x.bulk_insert(*args, k0 + k1, data[:1500])
            for kk in k0[:700]:
                pool.delete("a", kk)
                orc.delete(kk)
            same(pool.query_batch("a", q, k=K), orc.query_batch(q, K),
                 f"pool S={n_shards}")
        print("OK")
    """)
    assert "OK" in out
