"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.gather_distance import gather_distance_pallas


@pytest.mark.parametrize("n,d,b,k,dtype", [
    (128, 32, 8, 6, jnp.float32),
    (256, 64, 16, 10, jnp.float32),
    (64, 16, 4, 3, jnp.bfloat16),
    (512, 128, 8, 16, jnp.float32),
])
@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_gather_distance(n, d, b, k, dtype, metric):
    db = jax.random.normal(jax.random.PRNGKey(0), (n, d), dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, d), dtype)
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, k), 0, n)
    out = gather_distance_pallas(db, q, ids, metric=metric, interpret=True)
    exp = ref.gather_distance_ref(db, q, ids, metric=metric)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d,b,k,bq,bn", [
    (256, 32, 8, 5, 8, 64),
    (512, 64, 16, 10, 8, 128),
    (100, 16, 4, 4, 4, 25),       # non-pow2 tiling
])
@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_distance_topk(n, d, b, k, bq, bn, metric):
    db = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    q = jax.random.normal(jax.random.PRNGKey(1), (b, d))
    pd, pi = distance_topk_pallas(db, q, k, metric=metric, block_q=bq,
                                  block_n=bn, interpret=True)
    neg, j = jax.lax.top_k(-pd, k)
    got_d = -neg
    got_i = jnp.take_along_axis(pi, j, axis=1)
    exp_d, exp_i = ref.distance_topk_ref(db, q, k, metric=metric)
    np.testing.assert_allclose(np.sort(np.asarray(got_d)),
                               np.sort(np.asarray(exp_d)),
                               rtol=1e-4, atol=1e-4)
    assert (np.sort(np.asarray(got_i)) == np.sort(np.asarray(exp_i))).all()


@pytest.mark.parametrize("r,e,b,l,dtype", [
    (100, 32, 12, 6, jnp.float32),
    (1000, 64, 8, 4, jnp.float32),
    (50, 16, 6, 3, jnp.bfloat16),
])
@pytest.mark.parametrize("combine", ["sum", "mean"])
def test_embedding_bag(r, e, b, l, dtype, combine):
    table = jax.random.normal(jax.random.PRNGKey(0), (r, e), dtype)
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, l), 0, r)
    w = jax.random.uniform(jax.random.PRNGKey(2), (b, l))
    out = embedding_bag_pallas(table, ids, w, combine=combine, interpret=True)
    exp = ref.embedding_bag_ref(table, ids, w, combine=combine)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,kvh,dh,s,bs,cur", [
    (2, 8, 2, 32, 128, 32, 100),
    (3, 4, 4, 16, 64, 16, 64),    # MHA
    (1, 8, 1, 64, 256, 64, 7),    # MQA, short valid prefix
])
def test_flash_decode(b, h, kvh, dh, s, bs, cur):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, dh))
    out = flash_decode_pallas(q, k, v, jnp.asarray(cur), block_s=bs,
                              interpret=True)
    exp = ref.flash_decode_ref(q, k, v, jnp.asarray(cur))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


def test_flash_decode_per_sequence_cur_len():
    """Continuous-batching shape (DESIGN.md §11): cur_len is a [B] vector
    — each slot attends over its OWN live prefix. cur=1 is the floor the
    engine can pass (a parked slot decodes with n_valid=1, never 0).
    Must match per-row masking and the scalar fast path."""
    b, h, kvh, dh, s, bs = 4, 8, 2, 32, 128, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, dh))
    cur = jnp.asarray([100, 7, 1, 128], jnp.int32)
    out = flash_decode_pallas(q, k, v, cur, block_s=bs, interpret=True)
    exp = ref.flash_decode_ref(q, k, v, cur)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    # each row equals the scalar-cur_len result for that row alone
    for i in range(b):
        solo = flash_decode_pallas(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   jnp.asarray(int(cur[i])), block_s=bs,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(solo), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_distance_topk_prime_shapes(metric):
    """Regression (DESIGN.md §9 satellite): B or N prime used to collapse
    the block-shaving loop to 1-row blocks (a B×N program grid). The
    kernel now PADS to the tile multiple and masks the padded db rows —
    results must match the oracle and never leak a padded row id."""
    n, b, k = 997, 7, 5
    db = jax.random.normal(jax.random.PRNGKey(0), (n, 32))
    q = jax.random.normal(jax.random.PRNGKey(1), (b, 32))
    pd, pi = distance_topk_pallas(db, q, k, metric=metric, block_q=4,
                                  block_n=64, interpret=True)
    assert ((np.asarray(pi) >= 0) & (np.asarray(pi) < n)).all()
    neg, j = jax.lax.top_k(-pd, k)
    got_d = -neg
    got_i = jnp.take_along_axis(pi, j, axis=1)
    exp_d, exp_i = ref.distance_topk_ref(db, q, k, metric=metric)
    np.testing.assert_allclose(np.sort(np.asarray(got_d)),
                               np.sort(np.asarray(exp_d)),
                               rtol=1e-4, atol=1e-4)
    assert (np.sort(np.asarray(got_i)) == np.sort(np.asarray(exp_i))).all()


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_distance_topk_scales(metric):
    """Codec-encoded db + fused per-row decode (DESIGN.md §9): the int8
    kernel must equal the oracle on the decoded rows."""
    from repro.core.codec import get_codec

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 32)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    enc, scales = get_codec("int8").encode(x)
    dec = get_codec("int8").decode(enc, scales)
    pd, pi = distance_topk_pallas(jnp.asarray(enc), q, 6, metric=metric,
                                  scales=jnp.asarray(scales), block_q=4,
                                  block_n=64, interpret=True)
    neg, j = jax.lax.top_k(-pd, 6)
    exp_d, exp_i = ref.distance_topk_ref(jnp.asarray(dec), q, 6,
                                         metric=metric)
    np.testing.assert_allclose(np.sort(np.asarray(-neg)),
                               np.sort(np.asarray(exp_d)),
                               rtol=1e-4, atol=1e-4)
    got_i = jnp.take_along_axis(pi, j, axis=1)
    assert (np.sort(np.asarray(got_i)) == np.sort(np.asarray(exp_i))).all()


@pytest.mark.parametrize("n_valid", [3, 150])
def test_distance_topk_valid_mask(n_valid):
    """A row mask (the free slots of a sharded block) is applied inside
    the scan: masked rows never outrank a valid row, and kernel and
    oracle agree. With fewer valid rows than k the tail is masked rows
    at the padding distance."""
    n, k = 300, 8
    rng = np.random.default_rng(2)
    db = jnp.asarray(rng.normal(size=(n, 32)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    valid = np.zeros(n, bool)
    valid[rng.choice(n, n_valid, replace=False)] = True
    pd, pi = distance_topk_pallas(db, q, k, valid=jnp.asarray(valid),
                                  block_q=4, block_n=64, interpret=True)
    neg, j = jax.lax.top_k(-pd, k)
    got_d, got_i = np.asarray(-neg), np.asarray(
        jnp.take_along_axis(pi, j, axis=1))
    exp_d, exp_i = ref.distance_topk_ref(db, q, k, valid=jnp.asarray(valid))
    live = min(k, n_valid)
    assert valid[got_i[:, :live]].all()
    assert (got_d[:, live:] >= 3.0e38).all()
    np.testing.assert_allclose(got_d[:, :live], np.asarray(exp_d)[:, :live],
                               rtol=1e-5, atol=1e-5)
    assert (got_i[:, :live] == np.asarray(exp_i)[:, :live]).all()


def _running_case(case, metric, rng):
    """-> (db [N,D] f32, q [B,D], k, block_q, block_n, valid or None)."""
    if case == "worst":
        # every db tile beats every query row's running k-th k times:
        # distances fall strictly along the db for every query
        n, b = 320, 6
        u = rng.normal(size=32)
        u /= np.linalg.norm(u)
        noise = 0.001 * rng.normal(size=(n, 32))
        if metric == "cosine":      # d = 1 - c (q.u), c rising
            db = np.linspace(0.1, 10.0, n)[:, None] * u + noise
            q = u + 0.001 * rng.normal(size=(b, 32))
        else:                       # |q - x|^2 ~ r^2, r falling
            db = np.linspace(40.0, 1.0, n)[:, None] * u + noise
            q = 0.001 * rng.normal(size=(b, 32))
        return db, q, 8, 4, 64, None
    n, b, k, bq, bn = {"plain": (300, 8, 6, 8, 64),
                       "k_eq_block_n": (200, 5, 32, 8, 32),
                       "ragged_b": (300, 10, 6, 4, 64),
                       "sparse_head": (300, 7, 8, 4, 64)}[case]
    db = rng.normal(size=(n, 32))
    q = rng.normal(size=(b, 32))
    valid = None
    if case == "sparse_head":
        # the first two tiles hold 3 live rows between them, fewer than k
        valid = np.ones(n, bool)
        valid[:128] = False
        valid[[5, 70, 127]] = True
    return db, q, k, bq, bn, valid


@pytest.mark.parametrize("case", ["plain", "k_eq_block_n", "ragged_b",
                                  "sparse_head", "worst"])
@pytest.mark.parametrize("codec", ["fp32", "int8"])
@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_distance_topk_running(case, codec, metric):
    """The running top-k carried across db tiles is the exact top-k, in
    ``lax.top_k``'s order: ids equal the oracle's slot for slot, with the
    first tiles short of live rows, k == block_n, a query batch that is
    not a block multiple, and a db on which every tile improves every
    row (every tile runs all k passes)."""
    from repro.core.codec import get_codec

    rng = np.random.default_rng(7)
    db, q, k, bq, bn, valid = _running_case(case, metric, rng)
    db, q = db.astype(np.float32), jnp.asarray(q.astype(np.float32))
    scales = None
    if codec == "int8":
        db, s = get_codec("int8").encode(db)
        scales = jnp.asarray(s)
    vj = None if valid is None else jnp.asarray(valid)
    got_d, got_i = distance_topk_pallas(jnp.asarray(db), q, k, metric=metric,
                                        scales=scales, valid=vj, block_q=bq,
                                        block_n=bn, interpret=True)
    exp_d, exp_i = ref.distance_topk_ref(jnp.asarray(db), q, k,
                                         metric=metric, scales=scales,
                                         valid=vj)
    assert got_d.shape == got_i.shape == (q.shape[0], k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(exp_d),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(got_i) == np.asarray(exp_i)).all()


def test_distance_topk_passes_follow_the_data():
    """The pass counter: the first tile fills the running top-k in k
    passes; over a db sorted by ascending distance to the one query each
    later tile runs one pass that finds nothing to enter, and over the
    same db sorted by descending distance every tile runs k. The
    ``distance_topk.passes`` counter adds up the passes of each call."""
    from repro.core import dispatch
    from repro.kernels.distance_topk import distance_topk_passes

    rng = np.random.default_rng(3)
    n, k, bn = 512, 8, 64
    db = rng.normal(size=(n, 32)).astype(np.float32)
    q = rng.normal(size=(1, 32)).astype(np.float32)
    order = np.argsort(((db - q) ** 2).sum(1), kind="stable")
    tiles = n // bn
    for rows, want in [(order, [k] + [1] * (tiles - 1)),
                       (order[::-1], [k] * tiles)]:
        before = dispatch.get("distance_topk.passes")
        d, i, passes = distance_topk_passes(jnp.asarray(db[rows]),
                                            jnp.asarray(q), k, metric="l2",
                                            block_n=bn, interpret=True)
        assert np.asarray(passes).tolist() == [want]
        assert dispatch.get("distance_topk.passes") - before == sum(want)
        exp_d, exp_i = ref.distance_topk_ref(jnp.asarray(db[rows]),
                                             jnp.asarray(q), k, metric="l2")
        assert (np.asarray(i) == np.asarray(exp_i)).all()


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_gather_distance_scales(metric):
    """int8 rows + per-row scale DMA: fused decode inside the wave loop
    must equal the oracle's take+decode+dot (DESIGN.md §9)."""
    from repro.core.codec import get_codec

    rng = np.random.default_rng(1)
    x = rng.normal(size=(200, 24)).astype(np.float32)
    enc, scales = get_codec("int8").encode(x)
    q = jnp.asarray(rng.normal(size=(6, 24)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 200, size=(6, 9)).astype(np.int32))
    out = gather_distance_pallas(jnp.asarray(enc), q, ids, metric=metric,
                                 scales=jnp.asarray(scales), interpret=True)
    exp = ref.gather_distance_ref(jnp.asarray(enc), q, ids, metric=metric,
                                  scales=jnp.asarray(scales))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_resolve_interpret_platform_aware(monkeypatch):
    """interpret=None resolves per-platform (interpret only off-TPU) and
    honors the REPRO_PALLAS_INTERPRET env override."""
    from repro.kernels import resolve_interpret

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_interpret(None) == (not on_tpu)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False     # explicit arg still wins


def test_flat_topk_scales_dispatch(monkeypatch):
    """ops.flat_topk with scales: interpret == ref, like the f32 path."""
    from repro.core.codec import get_codec
    from repro.kernels import ops

    rng = np.random.default_rng(2)
    enc, scales = get_codec("int8").encode(
        rng.normal(size=(128, 32)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    d0, i0 = ops.flat_topk(jnp.asarray(enc), q, 5,
                           scales=jnp.asarray(scales))
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    d1, i1 = ops.flat_topk(jnp.asarray(enc), q, 5,
                           scales=jnp.asarray(scales))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5)
    assert (np.asarray(i0) == np.asarray(i1)).all()


def test_ops_dispatch_matches_ref(monkeypatch):
    """ops.* under REPRO_PALLAS=interpret must equal the reference (the
    variable unset)."""
    from repro.kernels import ops
    db = jax.random.normal(jax.random.PRNGKey(0), (128, 32))
    q = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    d0, i0 = ops.flat_topk(db, q, 5)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    d1, i1 = ops.flat_topk(db, q, 5)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5)
    assert (np.asarray(i0) == np.asarray(i1)).all()


@pytest.mark.parametrize("kernel", ["flash_decode", "embedding_bag"])
def test_interpret_none_resolves_like_retrieval_kernels(monkeypatch, kernel):
    """flash_decode_pallas and embedding_bag_pallas default to
    interpret=None and resolve it through ``resolve_interpret``, as the
    retrieval kernels do: the interpreter off-TPU (env override honoured),
    the compiled kernel on a TPU whatever the environment says."""
    import repro.kernels as kpkg
    from repro.kernels import embedding_bag as eb
    from repro.kernels import flash_decode as fd

    mod = fd if kernel == "flash_decode" else eb
    seen = []
    monkeypatch.setattr(mod, "_call", lambda *a: seen.append(a[-1]))

    def call():
        if kernel == "flash_decode":
            z = jnp.zeros((1, 2, 8))
            flash_decode_pallas(z, jnp.zeros((1, 8, 2, 8)),
                                jnp.zeros((1, 8, 2, 8)), jnp.asarray(8))
        else:
            embedding_bag_pallas(jnp.zeros((4, 8)),
                                 jnp.zeros((2, 3), jnp.int32))
        return seen.pop()

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(kpkg, "on_tpu", lambda: False)
    assert call() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert call() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(kpkg, "on_tpu", lambda: True)
    assert call() is False


def test_tpu_backend_ignores_env_switches(monkeypatch):
    """On a TPU backend no environment variable routes an op to the jnp
    reference or the interpreter: REPRO_PALLAS and REPRO_PALLAS_INTERPRET
    only steer CPU runs."""
    import repro.kernels as kpkg
    from repro.kernels import ops, resolve_interpret

    monkeypatch.setattr(kpkg, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    assert ops._use_pallas() == (True, False)
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    assert ops._use_pallas() == (True, False)
    for flag in ("1", "0"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", flag)
        assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True       # an explicit arg wins


@pytest.mark.parametrize("n,w", [(1, 10), (37, 10), (1000, 4), (64, 32)])
def test_packed_rows_take_and_set(n, w):
    """PackedRows holds an [N, W] table in 128-lane rows: every row reads
    back as stored, numpy and jax packing agree, and set_rows writes only
    the rows it names."""
    from repro.kernels.layout import pack_rows
    rng = np.random.default_rng(n)
    dense = rng.integers(-1, n, (n, w)).astype(np.int32)
    p_np, p_jx = pack_rows(dense), pack_rows(jnp.asarray(dense))
    assert p_np.table.shape == (-(-n // p_np.per), 128)
    assert np.array_equal(p_np.table, np.asarray(p_jx.table))
    packed = jax.tree.map(jnp.asarray, p_np)
    assert np.array_equal(np.asarray(packed.take(jnp.arange(n))), dense)
    rows = np.unique(rng.integers(0, n, max(n // 3, 1))).astype(np.int32)
    new = rng.integers(-1, n, (len(rows), w)).astype(np.int32)
    dense[rows] = new
    got = packed.set_rows(jnp.asarray(rows), jnp.asarray(new))
    assert np.array_equal(np.asarray(got.take(jnp.arange(n))), dense)
    # the lanes past W keep the fill
    tail = np.asarray(got.table).reshape(-1, got.lanes)[:, w:]
    assert (tail == -1).all()


def test_layout_views_are_bitcasts_at_device_capacity():
    """At ``device_capacity`` rows the row-tile, lane-row and packed views
    need no padding; the capacity rounds up to a multiple of 1024."""
    from repro.kernels.layout import (device_capacity, lane_rows, pack_rows,
                                      row_tiles)
    assert [device_capacity(n) for n in (0, 1, 1024, 1025, 1_000_001)] == \
        [1024, 1024, 1024, 2048, 1_000_448]
    cap = device_capacity(3000)
    v = jnp.arange(cap * 4, dtype=jnp.float32).reshape(cap, 4)
    assert row_tiles(v).shape == (cap // 8, 8, 4)
    assert np.array_equal(np.asarray(row_tiles(v)).reshape(cap, 4),
                          np.asarray(v))
    s = jnp.arange(cap, dtype=jnp.float32)
    assert np.array_equal(np.asarray(lane_rows(s)).reshape(-1), np.asarray(s))
    assert pack_rows(np.zeros((cap, 10), np.int32)).table.shape == \
        (cap // 8, 128)
