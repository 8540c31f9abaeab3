"""Multi-tenant IndexPool isolation contract (DESIGN.md §10).

Isolation is a *tested property*, not a convention. Asserted here:

  * parity — a pooled tenant behaves exactly like a dedicated flat
    index: same keys, distances equal up to the float32 rounding of the
    scan, same epoch schedule, and the canonical per-tenant state
    (``tenant_rows``) is bit-identical to the dedicated index's
    ``state_dict``. Both return the scan's own float32 top-k; the pool
    scans the tenant's padded slabs, and a product over another number
    of rows may round its last bit differently (DESIGN.md §10);
  * byte-absence, per tenant — after one tenant's retract + compact,
    the deleted vectors' bytes (raw fp32, normalized fp32, AND codec-
    encoded) appear in no arena host array, no packed device block, no
    snapshot page, and no WAL — while the *other* tenants sharing the
    arena are untouched (epochs do not move, caches stay valid);
  * evict → restore round-trips are bit-for-bit vs a never-evicted
    oracle (LRU paging is invisible to correctness);
  * slab reuse never leaks — a slab freed by tenant A's eviction and
    re-admitted to tenant B exposes none of A's rows or bytes, even
    before any compaction;
  * a randomized interleaved workload over ~20 tenants matches a
    per-tenant single-index oracle in results, epochs, and store bytes.

Sharded (S=8) variants run in subprocesses that set the fake-device XLA
flag before importing jax (same idiom as test_sharded.py).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _hypothesis_compat import HealthCheck, given, settings, st
from repro.core import IndexPool, make_index
from repro.core.hnsw_build import normalize_rows
from repro.data.synthetic import make_corpus
from repro.serve.retrieval import RetrievalEngine

DIM = 16
CODECS = ("fp32", "bf16", "int8")
DATA = make_corpus(40, DIM, seed=0)
EXTRA = make_corpus(16, DIM, seed=1)
SECRET = make_corpus(8, DIM, seed=7)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def walk_bytes(root):
    for dp, _, fns in os.walk(root):
        for fn in fns:
            p = os.path.join(dp, fn)
            with open(p, "rb") as f:
                yield p, f.read()


def oracle_for(codec, store=None, n_shards=1):
    return make_index("flat", store=store, dim=DIM, metric="cosine",
                      dtype=codec, n_shards=n_shards)


# the scan's float32 rounding: a few ulps of distances in [0, 2]
DIST_ATOL = 1e-6


def assert_same_answer(pool_out, oracle_out):
    """Same keys in the same order, distances within ``DIST_ATOL``."""
    (pk, pd), (ok, od) = pool_out, oracle_out
    assert pk == ok
    np.testing.assert_allclose(np.asarray(pd), np.asarray(od), rtol=0,
                               atol=DIST_ATOL)


def assert_tenant_bit_for_bit(pool, tid, oracle):
    """The pooled tenant's canonical state must be what the dedicated
    index would persist: same keys (insertion order, tombstones
    included), same array bytes, same epoch — and the same answers."""
    pool.admit(tid)
    keys, vecs, alive, enc, scales = pool._arena.tenant_rows(tid)
    oa, om = oracle.state_dict()
    assert keys == om["keys"]
    assert pool.epoch(tid) == oracle.mutation_epoch == om["epoch"]
    assert alive.tobytes() == np.asarray(oa["alive"]).tobytes()
    if "vectors" in oa:
        assert vecs.tobytes() == np.asarray(oa["vectors"]).tobytes()
    else:
        dec = oracle._codec.from_storage(np.asarray(oa["vectors_enc"]))
        assert enc.tobytes() == np.asarray(dec).tobytes()
        if scales is not None:
            assert scales.tobytes() == np.asarray(oa["scales"]).tobytes()
    if oracle.size == 0:                    # everything retracted: both
        assert pool.size(tid) == 0          # sides refuse queries alike
        return
    q = DATA[:5]
    assert_same_answer(pool.query_batch(tid, q, k=6),
                       oracle.query_batch(q, k=6))


def device_haystacks(pool):
    """Every device-visible buffer the arena publishes: packed blocks,
    gid maps, codec scale tables."""
    _, blocks, gids, scales = pool._arena.pack_arena()
    bufs = []
    for b in (blocks if isinstance(blocks, (list, tuple)) else [blocks]):
        bufs.append(np.asarray(b).tobytes())
    bufs.append(np.asarray(gids).tobytes())
    if scales is not None:
        bufs.append(np.asarray(scales).tobytes())
    return bufs


def secret_needles(vecs, enc=None):
    """Byte patterns that must vanish: raw fp32 rows, the normalized
    rows the fp32 pack publishes, and the codec-encoded rows."""
    needles = {}
    for i, v in enumerate(np.asarray(vecs, np.float32)):
        needles[f"fp32[{i}]"] = np.ascontiguousarray(v).tobytes()
        needles[f"norm[{i}]"] = np.ascontiguousarray(
            normalize_rows(v[None])[0]).tobytes()
        if enc is not None:
            needles[f"enc[{i}]"] = np.ascontiguousarray(enc[i]).tobytes()
    return needles


def assert_absent_everywhere(pool, needles, root=None):
    arena = pool._arena
    hay = {"arena._vecs": arena._vecs.tobytes()}
    if arena._enc is not None:
        hay["arena._enc"] = arena._enc.tobytes()
    if arena._scales is not None:
        hay["arena._scales"] = arena._scales.tobytes()
    for i, b in enumerate(device_haystacks(pool)):
        hay[f"device[{i}]"] = b
    if root is not None:
        for p, b in walk_bytes(root):
            hay[p] = b
    for nname, needle in needles.items():
        for hname, h in hay.items():
            assert needle not in h, f"{nname} found in {hname}"


# ---------------------------------------------------------------------------
# parity: a pooled tenant == a dedicated flat index
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_pool_matches_dedicated_index(codec):
    pool = IndexPool(dim=DIM, dtype=codec, slab_rows=8)
    oracles = {t: oracle_for(codec) for t in ("a", "b", "c")}
    for j, (tid, orc) in enumerate(oracles.items()):
        ks = [f"{tid}{i}" for i in range(10)]
        vs = DATA[j * 10:(j + 1) * 10]
        pool.bulk_insert(tid, ks, vs)
        orc.bulk_insert(ks, vs)
    # interleaved singles: the arena slabs interleave across tenants
    for j, (tid, orc) in enumerate(oracles.items()):
        pool.insert(tid, "solo", EXTRA[j])
        orc.insert("solo", EXTRA[j])
        pool.update(tid, f"{tid}3", EXTRA[j + 4])
        orc.update(f"{tid}3", EXTRA[j + 4])
        pool.delete(tid, f"{tid}7")
        orc.delete(f"{tid}7")
    for tid, orc in oracles.items():
        assert_tenant_bit_for_bit(pool, tid, orc)
        assert pool.size(tid) == orc.size
        assert pool.keys(tid) == orc.keys()
    # unknown tenants / bad ids are rejected, not silently created
    with pytest.raises(KeyError):
        pool.epoch("nobody")
    with pytest.raises(ValueError, match="tenant id"):
        pool.insert("with\x1fsep", "k", DATA[0])


@pytest.mark.parametrize("codec", CODECS)
def test_equal_distances_keep_the_tenants_row_order(codec):
    """A row that reuses a freed slot still ranks after the tenant's
    older rows: equal distances (a duplicate vector) come back in
    insertion order, as on a dedicated index, from ``query_batch`` and
    from a cross-tenant ``query_batch_multi``."""
    pool = IndexPool(dim=DIM, dtype=codec, slab_rows=8)
    orc = oracle_for(codec)
    ks = [f"a{i}" for i in range(6)]
    pool.bulk_insert("a", ks, DATA[:6])
    orc.bulk_insert(ks, DATA[:6])
    pool.bulk_insert("b", ["b0", "b1"], DATA[6:8])
    pool.delete("a", "a1")                  # frees a slot inside a's slab
    orc.delete("a1")
    pool.insert("a", "dup", DATA[3])        # ... which the pool reuses
    orc.insert("dup", DATA[3])
    q = DATA[3:4]
    ok, od = orc.query_batch(q, k=3)
    assert ok[0][:2] == ["a3", "dup"]
    assert_same_answer(pool.query_batch("a", q, k=3), (ok, od))
    mk, md = pool.query_batch_multi(np.stack([DATA[3], DATA[6]]),
                                    ["a", "b"], k=3)
    assert_same_answer((mk[:1], np.asarray(md)[:1]), (ok, od))


@pytest.mark.parametrize("codec", CODECS)
def test_cross_tenant_batch_matches_per_tenant_queries(codec):
    """query_batch_multi (one serving dispatch, rows from different
    tenants) returns exactly what per-tenant dispatches return."""
    pool = IndexPool(dim=DIM, dtype=codec, slab_rows=8)
    pool.bulk_insert("a", [f"a{i}" for i in range(12)], DATA[:12])
    pool.bulk_insert("b", [f"b{i}" for i in range(6)], DATA[12:18])
    pool.bulk_insert("c", [f"c{i}" for i in range(3)], DATA[18:21])
    q = DATA[:6] + 0.03 * EXTRA[:6]
    tenants = ["a", "b", "a", "c", "b", "a"]
    mk, md = pool.query_batch_multi(q, tenants, k=3)
    for i, tid in enumerate(tenants):
        sk, sd = pool.query_batch(tid, q[i:i + 1], k=3)
        assert mk[i] == sk[0], (codec, i)
        np.testing.assert_allclose(np.asarray(md)[i], np.asarray(sd)[0],
                                   rtol=1e-5, atol=1e-5)
        # every returned key belongs to the right namespace
        assert all(key.startswith(tid) for key in mk[i] if key)


# ---------------------------------------------------------------------------
# per-tenant epochs: one tenant's mutation never invalidates another
# ---------------------------------------------------------------------------
def test_per_tenant_epoch_independence():
    pool = IndexPool(dim=DIM)
    pool.bulk_insert("a", [f"a{i}" for i in range(6)], DATA[:6])
    pool.bulk_insert("b", [f"b{i}" for i in range(6)], DATA[6:12])
    ea, eb = pool.epoch("a"), pool.epoch("b")
    pool.delete("a", "a3")
    assert pool.epoch("a") == ea + 1
    assert pool.epoch("b") == eb            # untouched
    pool.compact("a")
    assert pool.epoch("b") == eb


def test_other_tenant_mutation_leaves_cache_hits_intact():
    """The serving-layer face of epoch independence: tenant A's delete
    drops only A's cached entries; B's identical-bytes query is still a
    cache hit served without a device dispatch."""
    pool = IndexPool(dim=DIM)
    pool.bulk_insert("a", [f"a{i}" for i in range(6)], DATA[:6])
    pool.bulk_insert("b", [f"b{i}" for i in range(6)], DATA[6:12])
    eng = RetrievalEngine(pool, max_batch=8)
    fa = eng.retrieve_one(DATA[0], k=2, tenant="a")
    fb = eng.retrieve_one(DATA[0], k=2, tenant="b")
    assert fa.keys[0].startswith("a") and fb.keys[0].startswith("b")
    pool.delete("a", fa.keys[0])
    again_b = eng.retrieve_one(DATA[0], k=2, tenant="b")
    assert again_b.from_cache and again_b.keys == fb.keys
    again_a = eng.retrieve_one(DATA[0], k=2, tenant="a")
    assert not again_a.from_cache
    assert fa.keys[0] not in again_a.keys   # retraction wins over cache
    assert eng.stats.invalidations == 1     # ONE tenant's entries dropped


# ---------------------------------------------------------------------------
# byte-absence: per-tenant retract + compact, arena shared with others
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_cross_tenant_byte_absence_after_compact(codec, tmp_path):
    root = str(tmp_path / "pool")
    pool = IndexPool(root, dim=DIM, dtype=codec, slab_rows=8)
    pool.bulk_insert("bob", [f"b{i}" for i in range(10)], DATA[:10])
    pool.bulk_insert("alice", [f"s{i}" for i in range(8)], SECRET)
    pool.bulk_insert("carol", [f"c{i}" for i in range(10)], DATA[10:20])
    _, _, _, enc, _ = pool._arena.tenant_rows("alice")
    needles = secret_needles(SECRET, enc)
    eb, ec = pool.epoch("bob"), pool.epoch("carol")
    pool.flush()                            # secrets hit disk first
    for i in range(8):
        pool.delete("alice", f"s{i}")
    pool.compact("alice")
    assert_absent_everywhere(pool, needles, root=root)
    # the *other* tenants sharing the arena are untouched
    assert pool.epoch("bob") == eb and pool.epoch("carol") == ec
    assert pool.size("bob") == 10 and pool.size("carol") == 10
    k, _ = pool.query_batch("bob", DATA[:3], k=3)
    assert all(key.startswith("b") for row in k for key in row)
    # and alice still exists (empty), able to take new rows
    assert pool.size("alice") == 0
    pool.insert("alice", "fresh", EXTRA[0])
    assert pool.query("alice", EXTRA[0], k=1)[0] == ["fresh"]


def test_deleted_rows_never_served_even_before_compact():
    """Before compaction the bytes legitimately persist (tombstones,
    WAL) — but no query path may RETURN a tombstoned row."""
    pool = IndexPool(dim=DIM, slab_rows=8)
    pool.bulk_insert("a", [f"a{i}" for i in range(8)], DATA[:8])
    pool.delete("a", "a0")
    keys, _ = pool.query_batch("a", DATA[:1], k=8)
    assert "a0" not in keys[0]
    mk, _ = pool.query_batch_multi(DATA[:1], ["a"], k=8)
    assert "a0" not in mk[0]


# ---------------------------------------------------------------------------
# LRU paging: evict -> restore is bit-for-bit, residency is invisible
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", CODECS)
def test_evict_restore_bit_for_bit(codec, tmp_path):
    pool = IndexPool(str(tmp_path / "pool"), dim=DIM, dtype=codec,
                     slab_rows=8)
    orc = oracle_for(codec)
    ks = [f"d{i}" for i in range(12)]
    pool.bulk_insert("t", ks, DATA[:12])
    orc.bulk_insert(ks, DATA[:12])
    pool.update("t", "d3", EXTRA[0])
    orc.update("d3", EXTRA[0])
    pool.delete("t", "d9")
    orc.delete("d9")
    pool.evict("t")
    assert "t" not in pool.resident_tenants()
    # churn the arena while t is paged out: its slab space is recycled
    pool.bulk_insert("noise", [f"n{i}" for i in range(16)], EXTRA)
    assert_tenant_bit_for_bit(pool, "t", orc)      # admits + compares
    # mutate after restore: epochs keep counting from where they were
    pool.insert("t", "post", EXTRA[1])
    orc.insert("post", EXTRA[1])
    assert_tenant_bit_for_bit(pool, "t", orc)
    # a second evict/restore cycle after compaction
    pool.delete("t", "d0")
    orc.delete("d0")
    pool.compact("t")
    orc.compact()
    pool.evict("t")
    assert_tenant_bit_for_bit(pool, "t", orc)


def test_multi_batch_splits_when_tenants_exceed_residency(tmp_path):
    """A cross-tenant tick touching more distinct tenants than
    max_resident must not fail: the pool splits it into sub-batches
    the LRU can page through, and results stitch back in input order."""
    pool = IndexPool(str(tmp_path / "pool"), dim=DIM, max_resident=2,
                     slab_rows=8)
    for j, tid in enumerate(("a", "b", "c", "d")):
        pool.bulk_insert(tid, [f"{tid}{i}" for i in range(4)],
                         DATA[j * 4:(j + 1) * 4])
    tenants = ["a", "b", "c", "d", "a", "c"]
    q = DATA[:6]
    mk, md = pool.query_batch_multi(q, tenants, k=2)
    assert len(mk) == 6 and np.asarray(md).shape == (6, 2)
    for i, tid in enumerate(tenants):
        sk, sd = pool.query_batch(tid, q[i:i + 1], k=2)
        assert mk[i] == sk[0], (i, tid)
        np.testing.assert_allclose(np.asarray(md)[i], np.asarray(sd)[0],
                                   rtol=1e-5, atol=1e-5)


def test_lru_admission_evicts_least_recently_used(tmp_path):
    pool = IndexPool(str(tmp_path / "pool"), dim=DIM, max_resident=2,
                     slab_rows=8)
    for j, tid in enumerate(("a", "b", "c")):
        pool.bulk_insert(tid, [f"{tid}{i}" for i in range(4)],
                         DATA[j * 4:(j + 1) * 4])
    assert pool.resident_tenants() == ["b", "c"]   # a paged out
    assert pool.stats["evictions"] == 1
    # touching a pages it back in and evicts the now-LRU b — by QUERY,
    # the paging is completely transparent
    k, _ = pool.query_batch("a", DATA[:1], k=2)
    assert k[0][0].startswith("a")
    assert pool.resident_tenants() == ["c", "a"]
    assert pool.size("b") == 4                     # b still fully intact


def test_slab_reuse_never_leaks_previous_owner(tmp_path):
    """A slab freed by tenant A's eviction and handed to tenant B must
    expose nothing of A — no keys in results (even with k far beyond
    B's size) and no bytes in any packed block — BEFORE any compaction."""
    pool = IndexPool(str(tmp_path / "pool"), dim=DIM, slab_rows=8)
    pool.bulk_insert("alice", [f"s{i}" for i in range(8)], SECRET)
    assert pool._arena._slab_owner[0][0] is not None
    pool.evict("alice")                    # slab returned to the pool
    pool.bulk_insert("bob", ["b0", "b1"], EXTRA[:2])
    # bob reuses freed capacity but the slab tail is zero-filled
    keys, dists = pool.query_batch("bob", SECRET[:4], k=8)
    for row in keys:
        assert all(key is None or key.startswith("b") for key in row)
    needles = secret_needles(SECRET)
    arena = pool._arena
    hay = {"arena._vecs": arena._vecs.tobytes()}
    for i, b in enumerate(device_haystacks(pool)):
        hay[f"device[{i}]"] = b
    for nn, needle in needles.items():
        for hn, h in hay.items():
            assert needle not in h, f"{nn} found in {hn}"
    # ...and alice was not destroyed: restore is intact (durability and
    # isolation are different axes)
    assert pool.size("alice") == 8


# ---------------------------------------------------------------------------
# sharded (S=8): same contract on a real mesh
# ---------------------------------------------------------------------------
SHARDED_CHECK = """
import numpy as np, os, tempfile
from repro.core import IndexPool, make_index
from repro.core.hnsw_build import normalize_rows

codec = {codec!r}
rng = np.random.default_rng(5)
data = rng.normal(size=(24, 16)).astype(np.float32)
sec = rng.normal(size=(8, 16)).astype(np.float32)
extra = rng.normal(size=(8, 16)).astype(np.float32)
root = tempfile.mkdtemp()

pool = IndexPool(root, dim=16, n_shards=8, dtype=codec, slab_rows=4)
oracle = make_index("flat", dim=16, metric="cosine", n_shards=8,
                    dtype=codec)
ks = [f"a{{i}}" for i in range(24)]
pool.bulk_insert("alice", ks, data)
oracle.bulk_insert(ks, data)
pool.bulk_insert("bob", [f"s{{i}}" for i in range(8)], sec)
pool.update("alice", "a3", extra[0]); oracle.update("a3", extra[0])
pool.delete("alice", "a9"); oracle.delete("a9")

# --- parity: keys exact, distances close, canonical state bitwise
q = data[:5] + 0.02 * extra[:5, :]
pk, pd = pool.query_batch("alice", q, k=6)
ok, od = oracle.query_batch(q, k=6)
assert pk == ok, (pk, ok)
np.testing.assert_allclose(np.asarray(pd), np.asarray(od),
                           rtol=1e-5, atol=1e-5)
keys, vecs, alive, enc, scales = pool._arena.tenant_rows("alice")
oa, om = oracle.state_dict()
assert keys == om["keys"] and pool.epoch("alice") == om["epoch"]
assert alive.tobytes() == np.asarray(oa["alive"]).tobytes()
if "vectors" in oa:
    assert vecs.tobytes() == np.asarray(oa["vectors"]).tobytes()
else:
    dec = oracle._codec.from_storage(np.asarray(oa["vectors_enc"]))
    assert enc.tobytes() == np.asarray(dec).tobytes()

# --- evict -> restore bit-for-bit under churn
before = pool._arena.tenant_rows("alice")
ep = pool.epoch("alice")
pool.evict("alice")
pool.bulk_insert("noise", [f"n{{i}}" for i in range(8)], extra)
pool.admit("alice")
after = pool._arena.tenant_rows("alice")
assert before[0] == after[0] and ep == pool.epoch("alice")
for x, y in zip(before[1:], after[1:]):
    assert (x is None) == (y is None)
    if x is not None:
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
pk2, pd2 = pool.query_batch("alice", q, k=6)
assert pk2 == pk
assert np.asarray(pd2).tobytes() == np.asarray(pd).tobytes()

# --- per-tenant byte-absence after retract + compact, across all shards
_, _, _, senc, _ = pool._arena.tenant_rows("bob")
needles = []
for i in range(8):
    needles.append(np.ascontiguousarray(sec[i]).tobytes())
    needles.append(np.ascontiguousarray(normalize_rows(sec[i:i+1])[0])
                   .tobytes())
    if senc is not None:
        needles.append(np.ascontiguousarray(senc[i]).tobytes())
pool.flush()
for i in range(8):
    pool.delete("bob", f"s{{i}}")
pool.compact("bob")
arena = pool._arena
hay = [arena._vecs.tobytes()]
if arena._enc is not None:
    hay.append(arena._enc.tobytes())
_, blocks, gids, scl = arena.pack_arena()
for b in (blocks if isinstance(blocks, (list, tuple)) else [blocks]):
    hay.append(np.asarray(b).tobytes())
if scl is not None:
    hay.append(np.asarray(scl).tobytes())
for dp, _, fns in os.walk(root):
    for fn in fns:
        with open(os.path.join(dp, fn), "rb") as f:
            hay.append(f.read())
for n in needles:
    assert all(n not in h for h in hay)
# alice unaffected by bob's compaction
pk3, _ = pool.query_batch("alice", q, k=6)
assert pk3 == pk
print("OK")
"""


@pytest.mark.parametrize("codec", CODECS)
def test_sharded_isolation_contract(codec):
    out = run_sub(SHARDED_CHECK.format(codec=codec))
    assert "OK" in out


# ---------------------------------------------------------------------------
# randomized interleaved workload vs per-tenant single-index oracle
# ---------------------------------------------------------------------------
def _apply_workload(pool, oracles, stores, steps, rng, check_every=True):
    """Interleave insert/bulk/update/delete/query/evict/admit/compact
    across every tenant, mirroring each op on the oracle; queries and
    epochs are compared as we go."""
    tids = list(oracles)
    vecs = make_corpus(256, DIM, seed=int(rng.integers(1 << 30)))
    counters = dict.fromkeys(tids, 0)
    for _ in range(steps):
        tid = tids[int(rng.integers(len(tids)))]
        orc = oracles[tid]
        live = orc.keys()
        op = int(rng.integers(8))
        if op == 0 or not live:                        # insert
            key = f"k{counters[tid]}"
            counters[tid] += 1
            v = vecs[int(rng.integers(len(vecs)))]
            pool.insert(tid, key, v)
            orc.insert(key, v)
        elif op == 1:                                  # bulk (dups ok)
            n = int(rng.integers(1, 5))
            ks = [f"k{counters[tid] + j}" for j in range(n)]
            counters[tid] += n
            vs = vecs[rng.integers(0, len(vecs), n)]
            pool.bulk_insert(tid, ks, vs)
            orc.bulk_insert(ks, vs)
        elif op == 2:                                  # update
            key = live[int(rng.integers(len(live)))]
            v = vecs[int(rng.integers(len(vecs)))]
            pool.update(tid, key, v)
            orc.update(key, v)
        elif op == 3:                                  # delete
            key = live[int(rng.integers(len(live)))]
            pool.delete(tid, key)
            orc.delete(key)
        elif op == 4:                                  # query
            q = vecs[rng.integers(0, len(vecs), 3)]
            k = int(rng.integers(1, 6))
            assert_same_answer(pool.query_batch(tid, q, k=k),
                               orc.query_batch(q, k=k))
        elif op == 5:                                  # evict (page out)
            if tid in pool.resident_tenants():
                pool.evict(tid)
                if stores is not None:
                    stores[tid].snapshot(orc)
        elif op == 6:                                  # admit (page in)
            pool.admit(tid)
        elif op == 7:                                  # compact
            pool.compact(tid)
            orc.compact()
        if check_every:
            assert pool.epoch(tid) == orc.mutation_epoch, tid


def _npz_equal(a_bytes, b_bytes):
    import io
    a, b = np.load(io.BytesIO(a_bytes)), np.load(io.BytesIO(b_bytes))
    if a.files != b.files:
        return False
    return all(a[f].dtype == b[f].dtype and a[f].shape == b[f].shape
               and a[f].tobytes() == b[f].tobytes() for f in a.files)


def assert_same_store_tree(pool_dir, oracle_dir):
    """Same file set; byte-identical except .npz pages, which are
    zip-archive nondeterministic (timestamps) and compare as parsed
    arrays."""
    pa = {os.path.relpath(p, pool_dir): b for p, b in walk_bytes(pool_dir)}
    ob = {os.path.relpath(p, oracle_dir): b
          for p, b in walk_bytes(oracle_dir)}
    assert set(pa) == set(ob), (set(pa) ^ set(ob))
    for rel in pa:
        if rel.endswith(".npz"):
            assert _npz_equal(pa[rel], ob[rel]), rel
        else:
            assert pa[rel] == ob[rel], rel


def test_randomized_workload_matches_oracle_seeded(tmp_path):
    """Seeded 20-tenant interleaved workload: every query result and
    every epoch matches a dedicated per-tenant index, and at shutdown
    every tenant's store dir holds the same bytes a dedicated store
    would (WAL, config, manifests byte-identical; pages array-equal)."""
    from repro.store import IndexStore

    rng = np.random.default_rng(12)
    tids = [f"t{i}" for i in range(20)]
    pool = IndexPool(str(tmp_path / "pool"), dim=DIM, dtype="int8",
                     slab_rows=8, max_resident=32)
    oracles, stores = {}, {}
    for tid in tids:
        stores[tid] = IndexStore(str(tmp_path / "oracle" / tid),
                                 page_bytes=4 << 20)
        oracles[tid] = oracle_for("int8", store=stores[tid])
        # seed every tenant non-empty so all ops are exercised
        ks = [f"seed{j}" for j in range(3)]
        vs = DATA[rng.integers(0, len(DATA), 3)]
        pool.bulk_insert(tid, ks, vs)
        oracles[tid].bulk_insert(ks, vs)
    _apply_workload(pool, oracles, stores, steps=200, rng=rng)
    pool.flush()
    for tid in tids:
        stores[tid].snapshot(oracles[tid])
        assert_tenant_bit_for_bit(pool, tid, oracles[tid])
        assert_same_store_tree(
            str(tmp_path / "pool" / "tenants" / tid),
            str(tmp_path / "oracle" / tid))


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(0, 2**31 - 1), steps=st.integers(5, 60),
       n_tenants=st.integers(2, 8))
def test_randomized_workload_matches_oracle_hypothesis(seed, steps,
                                                       n_tenants):
    """Property form of the same contract (skips cleanly when hypothesis
    is not installed — the seeded test above always runs). No store
    root: eviction pages to host spill, the durability-free fast path."""
    rng = np.random.default_rng(seed)
    pool = IndexPool(dim=DIM, dtype="fp32", slab_rows=8)
    oracles = {}
    for i in range(n_tenants):
        tid = f"t{i}"
        oracles[tid] = oracle_for("fp32")
        pool.insert(tid, "seed", DATA[i])
        oracles[tid].insert("seed", DATA[i])
    _apply_workload(pool, oracles, None, steps=steps, rng=rng)
    for tid, orc in oracles.items():
        assert_tenant_bit_for_bit(pool, tid, orc)
