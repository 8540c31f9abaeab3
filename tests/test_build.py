"""Device-resident bulk ingest (DESIGN.md §13).

Parity pins for the vectorized construction path:
  * the batched neighbor-select op vs the host Alg. 4 oracle —
    bit-for-bit, on integer-valued vectors so fp32 arithmetic is exact
    in ANY summation order (np vs XLA dot products cannot diverge);
  * the vectorized reciprocal connect vs the retained host-loop oracle
    — bit-for-bit on random graphs + random edge lists;
  * bulk-vs-sequential recall across awkward batch shapes (1-row tail,
    non-divisible N, batch > N) and codecs;
  * the bootstrap-capped k_cand regression, max_level_cap threading,
    run-to-run determinism (the WAL-replay contract), and the
    adjacency-only H2D accounting.

Sharded reshard-adoption of a bulk-built graph runs in a subprocess
with forced fake devices (the tests/test_sharded.py idiom).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import dispatch
from repro.core import hnsw as jhnsw
from repro.core import hnsw_build as hb
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _int_vectors(rng, n, d, lo=-4, hi=5):
    """Integer-valued fp32 rows: every dot product is an exact small
    integer, so host numpy and XLA produce identical distances and the
    bit-for-bit pins below cannot flake on summation order."""
    return rng.integers(lo, hi, size=(n, d)).astype(np.float32)


def _exact10(data, q, metric="cosine"):
    if metric == "cosine":
        vn = hb.normalize_rows(data)
        qn = hb.normalize_rows(q)
        d = 1.0 - qn @ vn.T
    elif metric == "ip":
        d = 1.0 - q @ data.T
    else:
        d = ((q[:, None, :] - data[None]) ** 2).sum(-1)
    return np.argsort(d, axis=1, kind="stable")[:, :10]


def _recall(g, q, true10):
    ids, _ = jhnsw.search_graph(jhnsw.to_device_graph(g), q, k=10, ef=64)
    return jhnsw.recall_at_k(np.asarray(ids), true10)


# ---------------------------------------------------------------- select op
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_select_op_matches_host_oracle(metric):
    """ops.select_neighbors == select_heuristic_host per row, including
    -1 padding, duplicate ids, all-invalid rows, and C < m."""
    rng = np.random.default_rng(3)
    n, d, b, c, m = 80, 16, 64, 24, 8
    vectors = _int_vectors(rng, n, d)
    q = _int_vectors(rng, b, d)
    cand = rng.integers(-1, n, size=(b, c)).astype(np.int32)
    cand[0] = -1                                   # fully invalid row
    cand[1, 5:] = cand[1, 4]                       # heavy duplication
    ids, dists = ops.select_neighbors(vectors, q, cand, m=m, metric=metric)
    ids = np.asarray(ids)
    for j in range(b):
        cj = cand[j][cand[j] >= 0]
        cd = list(zip(hb._dist(metric, q[j], vectors[cj]),
                      [int(x) for x in cj]))
        want = hb.select_heuristic_host(metric, vectors, q[j], cd, m)
        got = ids[j][ids[j] >= 0]
        assert np.array_equal(got, want), (j, got, want)
    # width narrower than m still yields well-formed -1-padded output
    ids2, _ = ops.select_neighbors(vectors, q, cand[:, :3], m=m,
                                   metric=metric)
    ids2 = np.asarray(ids2)
    assert ids2.shape == (b, m)
    assert (ids2[0] == -1).all()


# ------------------------------------------------------- reciprocal connect
def _random_builder(rng, n=60, d=12, M=4, metric="l2"):
    b = hb.SequentialBuilder(d, M=M, ef_construction=16, metric=metric,
                             capacity=n, max_level_cap=4, seed=0)
    b.vectors[:n] = _int_vectors(rng, n, d)
    b.levels[:n] = rng.integers(0, 3, size=n)
    b.n, b.entry, b.max_level = n, 0, int(b.levels[:n].max())
    for node in range(n):
        nb0 = rng.choice(n, size=rng.integers(0, 2 * M + 1), replace=False)
        b.neighbors0[node, : len(nb0)] = nb0
        for lc in range(1, int(b.levels[node]) + 1):
            el = np.flatnonzero(b.levels[:n] >= lc)
            up = rng.choice(el, size=min(len(el), rng.integers(0, M + 1)),
                            replace=False)
            b.upper[lc - 1, node, : len(up)] = up
    return b


def test_connect_op_vs_host_oracle_bitforbit():
    """_connect_reciprocal impl='op' == impl='host' on random graphs +
    random back-edge lists (both layers, shared destinations)."""
    import copy

    rng = np.random.default_rng(11)
    for trial in range(3):
        b1 = _random_builder(np.random.default_rng(100 + trial))
        b2 = copy.deepcopy(b1)
        n = b1.n
        ne = 40
        e_dst = rng.integers(0, n, size=ne).astype(np.int32)
        e_lay = np.minimum(rng.integers(0, 3, size=ne),
                           b1.levels[e_dst]).astype(np.int32)
        e_src = rng.integers(0, n, size=ne).astype(np.int32)
        keep = e_src != e_dst
        e_src, e_dst, e_lay = e_src[keep], e_dst[keep], e_lay[keep]
        import jax.numpy as jnp
        d1 = hb._connect_reciprocal(b1, e_src, e_dst, e_lay,
                                    dev_vectors=jnp.asarray(b1.vectors),
                                    impl="op")
        d2 = hb._connect_reciprocal(b2, e_src, e_dst, e_lay, impl="host")
        assert sorted(d1) == sorted(d2)
        assert np.array_equal(b1.neighbors0, b2.neighbors0)
        assert np.array_equal(b1.upper, b2.upper)


# ------------------------------------------------------------ build parity
@pytest.mark.parametrize("n,batch", [(600, 650),   # batch > N
                                     (600, 250),   # non-divisible tail
                                     (601, 200)])  # 1-row tail
def test_bulk_recall_parity_batch_shapes(n, batch, rng):
    data = rng.normal(size=(n, 32)).astype(np.float32)
    q = rng.normal(size=(50, 32)).astype(np.float32)
    true10 = _exact10(data, q)
    r_seq = _recall(hb.build_sequential(data, M=8, ef_construction=40,
                                        seed=1), q, true10)
    g = hb.bulk_build(data, M=8, ef_construction=40, seed=1,
                      bootstrap=64, batch_size=batch)
    assert g.n == n
    r_blk = _recall(g, q, true10)
    assert r_blk >= r_seq - 0.05, (r_blk, r_seq)


def test_bulk_determinism_and_connect_impl_parity(rng):
    """Same inputs -> bit-identical graph (the WAL-replay contract), and
    the vectorized connect matches the host-loop oracle end-to-end."""
    data = rng.normal(size=(400, 24)).astype(np.float32)
    kw = dict(M=6, ef_construction=30, seed=3, bootstrap=32, batch_size=128)
    g1 = hb.bulk_build(data, **kw)
    g2 = hb.bulk_build(data, **kw)
    g3 = hb.bulk_build(data, connect_impl="host", **kw)
    for ga, gb in [(g1, g2), (g1, g3)]:
        assert np.array_equal(ga.neighbors0, gb.neighbors0)
        assert np.array_equal(ga.upper, gb.upper)
        assert np.array_equal(ga.levels, gb.levels)
        assert ga.entry == gb.entry and ga.max_level == gb.max_level


def test_k_cand_tracks_live_prefix(monkeypatch, rng):
    """Regression: the candidate count must cap against the LIVE prefix,
    not the bootstrap size — bootstrap=16, efC=100 used to build every
    batch from 16 candidates forever."""
    seen = []
    orig = jhnsw.search_graph

    def spy(g, queries, k=10, ef=64, **kw):
        seen.append(k)
        return orig(g, queries, k=k, ef=ef, **kw)

    monkeypatch.setattr(jhnsw, "search_graph", spy)
    data = rng.normal(size=(500, 16)).astype(np.float32)
    hb.bulk_build(data, M=4, ef_construction=100, seed=0,
                  bootstrap=16, batch_size=128)
    assert seen[0] == 16            # first batch: only the bootstrap exists
    assert max(seen) == 100         # later batches reach the full efC
    assert seen == sorted(seen)     # cap grows with the prefix


def test_max_level_cap_threading(rng):
    """bulk_build draws levels from the same stream as SequentialBuilder
    and honors max_level_cap (it was hardcoded 12)."""
    data = rng.normal(size=(500, 16)).astype(np.float32)
    g_seq = hb.build_sequential(data, M=4, ef_construction=20, seed=5)
    g_blk = hb.bulk_build(data, M=4, ef_construction=20, seed=5,
                          bootstrap=16, batch_size=128)
    assert np.array_equal(g_blk.levels, g_seq.levels)  # same per-row draws
    g_cap = hb.bulk_build(data, M=4, ef_construction=20, seed=5,
                          bootstrap=16, batch_size=128, max_level_cap=1)
    assert np.array_equal(g_cap.levels, np.minimum(g_seq.levels, 1))
    assert g_cap.max_level <= 1


def test_bulk_build_interface_codecs(rng):
    """use_bulk_build through the HNSW interface at fp32 and int8: bulk
    adoption, query recall vs the exact oracle, and appends after
    adoption keep working."""
    from repro.core.interface import HNSW

    data = rng.normal(size=(400, 24)).astype(np.float32)
    q = rng.normal(size=(30, 24)).astype(np.float32)
    true10 = _exact10(data, q)
    for dtype, floor in [("fp32", 0.85), ("int8", 0.75)]:
        idx = HNSW(M=8, ef_construction=40, use_bulk_build=True,
                   dtype=dtype)
        idx.bulk_insert([f"d{i}" for i in range(len(data))], data)
        keys, _ = idx.query_batch(q, k=10)
        ids = np.asarray([[int(k[1:]) if k is not None else -1 for k in row]
                          for row in keys])
        assert jhnsw.recall_at_k(ids, true10) >= floor
        idx.insert("extra", rng.normal(size=24).astype(np.float32))
        assert idx.size == len(data) + 1
        k2, _ = idx.query(rng.normal(size=24).astype(np.float32), k=5)
        assert len(k2) == 5


# ------------------------------------------------------------- H2D account
def test_adjacency_updates_and_h2d_accounting(rng):
    data = rng.normal(size=(200, 16)).astype(np.float32)
    g = hb.build_sequential(data, M=4, ef_construction=20, seed=0)
    dispatch.reset("hnsw.h2d_bytes")
    dg = jhnsw.to_device_graph(g)
    full = dispatch.get("hnsw.h2d_bytes")
    lmax = g.upper.shape[0]
    assert full == 200 * (16 * 4 + 4 * 8 + 4 * lmax * 4 + 4)
    # adjacency-only scatter: ships int32 rows, leaves vectors alone
    g.neighbors0[7] = -1
    g.neighbors0[7, 0] = 3
    before = np.asarray(dg.vectors).copy()
    dispatch.reset("hnsw.h2d_bytes")
    dg = jhnsw.apply_adjacency_updates(dg, g, [7])
    adj_bytes = dispatch.get("hnsw.h2d_bytes")
    assert adj_bytes == 1 * 4 * (8 + lmax * 4)     # one row, no [D] payload
    row = np.asarray(dg.neighbors0.take(7))
    assert row[0] == 3 and (row[1:] == -1).all()
    assert np.array_equal(np.asarray(dg.vectors), before)
    # the bulk path's whole-build traffic: one capacity upload + O(M)
    # int32 per inserted row, nowhere near the legacy O(batches) full
    # re-uploads (enough batches here that the ratio is unambiguous)
    data = rng.normal(size=(1000, 16)).astype(np.float32)
    dispatch.reset("hnsw.h2d_bytes")
    hb.bulk_build(data, M=4, ef_construction=20, seed=0,
                  bootstrap=32, batch_size=64)
    blk = dispatch.get("hnsw.h2d_bytes")
    dispatch.reset("hnsw.h2d_bytes")
    hb.bulk_build_legacy(data, M=4, ef_construction=20, seed=0,
                         bootstrap=32, batch_size=64)
    leg = dispatch.get("hnsw.h2d_bytes")
    assert blk < leg / 2, (blk, leg)


# ------------------------------------------------------- sharded adoption
def test_device_graph_owns_aligned_packed_layout(monkeypatch, rng):
    """A host graph of N % 8 != 0 rows goes up at ``device_capacity`` rows
    with its adjacency packed: the extra rows are zero, edgeless and
    tombstoned, dirty rows scatter into the packed tables, and the
    kernels (under the interpreter) search the padded graph exactly as
    their jnp oracles do."""
    from repro.kernels.layout import device_capacity
    data = rng.normal(size=(1001, 16)).astype(np.float32)
    g = hb.build_sequential(data, M=4, ef_construction=20, seed=0)
    dg = jhnsw.to_device_graph(g)
    cap = device_capacity(1001)
    assert dg.n == cap == 1024 and dg.fits(g)
    assert dg.neighbors0.table.shape == (cap // dg.neighbors0.per, 128)
    assert dg.n_layers == g.upper.shape[0]
    up = np.asarray(dg.upper.take(np.arange(dg.upper.rows)))
    up = up.reshape(dg.n_layers, cap, -1)
    assert np.array_equal(up[:, :1001], g.upper)
    assert (up[:, 1001:] == -1).all()
    nb = np.asarray(dg.neighbors0.take(np.arange(cap)))
    assert np.array_equal(nb[:1001], g.neighbors0)
    assert (nb[1001:] == -1).all()
    assert np.asarray(dg.deleted)[1001:].all()
    assert not np.asarray(dg.deleted)[:1001].any()
    assert (np.asarray(dg.vectors)[1001:] == 0).all()
    g.neighbors0[1000] = -1
    g.neighbors0[1000, :2] = (3, 5)
    dg = jhnsw.apply_adjacency_updates(dg, g, [1000])
    assert np.array_equal(np.asarray(dg.neighbors0.take(1000)),
                          g.neighbors0[1000])
    q = data[:8] + 0.01
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    ids_r, d_r = jhnsw.search_graph(dg, q, k=10, ef=32)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    ids_k, d_k = jhnsw.search_graph(dg, q, k=10, ef=32)
    assert (np.asarray(ids_k) < 1001).all()
    assert np.array_equal(np.asarray(ids_k), np.asarray(ids_r))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), atol=1e-5)


def test_reshard_adopts_bulk_built_graph():
    """A 1-shard bulk-built fp32 snapshot restored at n_shards=4 takes
    the bulk-adoption fast path: canonical key order survives, exact
    results match the original, ANN stays sane, and every child builder
    came from a bulk-built graph."""
    code = """
        import numpy as np
        from repro.core.interface import HNSW
        from repro.core import hnsw_build as hb

        calls = []
        orig = hb.bulk_build
        def spy(*a, **k):
            calls.append(len(a[0]))
            return orig(*a, **k)
        hb.bulk_build = spy

        rng = np.random.default_rng(0)
        data = rng.normal(size=(300, 16)).astype(np.float32)
        keys = [f"d{i}" for i in range(len(data))]
        one = HNSW(M=6, ef_construction=30, use_bulk_build=True)
        one.bulk_insert(keys, data)
        arrays, meta = one.state_dict()
        assert calls == [300]

        four = HNSW(M=6, ef_construction=30, use_bulk_build=True,
                    n_shards=4)
        four.restore_state(arrays, meta)
        # children were bulk-adopted (one bulk_build per non-empty shard)
        assert len(calls) == 1 + sum(
            1 for c in four._shards if c._builder is not None), calls
        assert sum(calls[1:]) == 300
        assert four.keys() == one.keys()
        q = rng.normal(size=(8, 16)).astype(np.float32)
        for b in range(8):
            k1, d1 = one.exact_query(q[b], k=5)
            k4, d4 = four.exact_query(q[b], k=5)
            assert k1 == k4, (k1, k4)
            np.testing.assert_allclose(d1, d4, rtol=1e-5, atol=1e-5)
        kk, _ = four.query_batch(q, k=5)
        assert all(len(r) == 5 for r in kk)
        # mutations after adoption keep routing/behaving
        four.delete("d3")
        assert four.size == 299
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
