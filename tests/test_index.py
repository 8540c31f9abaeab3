"""VectorIndex protocol conformance (DESIGN.md §1) across all four
backends, mutation semantics (tombstones, update, export round-trip), and
the HNSW incremental device-graph sync parity (DESIGN.md §3)."""
import os
import tempfile

import numpy as np
import pytest

from repro.core import INDEX_KINDS, make_index, make_index_from_config
from repro.core import hnsw as jhnsw
from repro.data.synthetic import make_corpus

KINDS = list(INDEX_KINDS)


def build(kind, dim=16, n=150, seed=0):
    data = make_corpus(n, dim, seed=seed)
    idx = make_index(kind, dim=dim, metric="cosine", M=8,
                     ef_construction=60, ef_search=48)
    idx.bulk_insert([f"d{i}" for i in range(n)], data)
    return idx, data


# ---------------------------------------------------------------------------
# shared conformance: insert / update / delete / query / export / load
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", KINDS)
def test_conformance_insert_query(kind):
    idx, data = build(kind)
    assert idx.size == 150 and len(idx) == 150
    keys, dists = idx.query(data[7], k=5)
    assert keys[0] == "d7" and float(dists[0]) < 1e-4
    assert len(keys) == len(dists)
    # single-key insert is an upsert path shared by every backend
    idx.insert("extra", data[7] + 0.001)
    assert idx.size == 151 and "extra" in idx
    # batched queries return lists of lists
    bk, bd = idx.query(data[:3], k=4)
    assert len(bk) == 3 and bk[1][0] == "d1"


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_delete_excludes_tombstoned(kind):
    idx, data = build(kind)
    before, _ = idx.query(data[7], k=5)
    assert before[0] == "d7"
    idx.delete("d7")
    after, _ = idx.query(data[7], k=5)
    assert "d7" not in after
    assert idx.size == 149 and "d7" not in idx.keys()
    with pytest.raises(KeyError):
        idx.delete("d7")                    # double delete is an error
    exact, _ = idx.exact_query(data[7], k=5)
    assert "d7" not in exact                # the oracle honors tombstones too


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_update_changes_neighbor(kind):
    idx, data = build(kind)
    probe = make_corpus(1, 16, seed=99)[0]
    winner, _ = idx.query(probe, k=1)
    # move a different key exactly onto the probe: it must take over top-1
    mover = "d33" if winner[0] != "d33" else "d44"
    idx.update(mover, probe)
    got, d = idx.query(probe, k=1)
    assert got[0] == mover and float(d[0]) < 1e-4
    assert idx.size == 150                  # update is not an insert
    with pytest.raises(KeyError):
        idx.update("never-inserted", probe)


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_export_load_roundtrip(kind):
    idx, data = build(kind)
    idx.delete("d3")
    idx.update("d5", data[3])
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "idx.npz")
        idx.export(p)
        idx2 = type(idx).load(p)
        assert idx2.size == idx.size == 149
        k1, d1 = idx.query(data[3], k=5)
        k2, d2 = idx2.query(data[3], k=5)
        assert k1 == k2 and k2[0] == "d5"
        np.testing.assert_allclose(d1, d2, rtol=1e-6)
        assert "d3" not in k2               # tombstones round-trip
        assert set(idx2.keys()) == set(idx.keys())


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_query_matches_exact_oracle(kind):
    idx, data = build(kind)
    rng = np.random.default_rng(5)
    hits = total = 0
    for qi in rng.integers(0, 150, 10):
        q = data[qi] + 0.05 * rng.normal(size=16).astype(np.float32)
        keys, _ = idx.query(q, k=5)
        exact, _ = idx.exact_query(q, k=5)
        hits += len({k for k in keys if k} & set(exact))
        total += 5
    assert hits / total >= 0.8, (kind, hits / total)


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_empty_index_errors(kind):
    idx = make_index(kind, dim=8, metric="cosine")
    with pytest.raises(ValueError, match="empty"):
        idx.query(np.zeros(8, np.float32), k=1)
    with pytest.raises(ValueError, match="empty"):
        idx.exact_query(np.zeros(8, np.float32), k=1)
    with pytest.raises(ValueError, match="empty"):
        idx.export("/tmp/never-written.npz")
    assert idx.size == 0


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_k_exceeding_live_pads_with_none(kind):
    data = make_corpus(5, 16, seed=8)
    idx = make_index(kind, dim=16, metric="cosine", M=4, ef_construction=20)
    idx.bulk_insert([f"d{i}" for i in range(5)], data)
    idx.delete("d4")
    keys, dists = idx.query(data[0], k=10)
    assert len(keys) == len(dists) == 10       # fixed k slots, every backend
    assert keys[0] == "d0" and keys[4:] == [None] * 6


@pytest.mark.parametrize("kind", KINDS)
def test_conformance_bulk_insert_duplicate_key_collapses(kind):
    """A key repeated within one bulk_insert batch is an upsert: exactly
    one live row survives (last value wins) and delete retracts it fully
    — no ghost row that a query can still surface."""
    data = make_corpus(12, 16, seed=11)
    idx = make_index(kind, dim=16, metric="cosine", M=4, ef_construction=20)
    idx.bulk_insert(["a", "a"] + [f"d{i}" for i in range(10)],
                    np.concatenate([data[:2], data[2:]]))
    assert idx.size == 11
    assert idx.keys().count("a") == 1
    got, d = idx.query(data[1], k=1)       # the LAST duplicate's vector won
    assert got[0] == "a" and float(d[0]) < 1e-4
    idx.delete("a")
    keys, _ = idx.query(data[0], k=idx.size)
    assert "a" not in keys                 # the first dup left no ghost
    keys, _ = idx.query(data[1], k=idx.size)
    assert "a" not in keys


def test_hnsw_bulk_build_duplicate_key_collapses():
    """Same contract through the bulk-build adoption fast path."""
    from repro.core.interface import HNSW
    data = make_corpus(12, 16, seed=12)
    idx = HNSW(distance_function="cosine", M=4, ef_construction=20,
               use_bulk_build=True)
    idx.bulk_insert(["a", "a"] + [f"d{i}" for i in range(10)],
                    np.concatenate([data[:2], data[2:]]))
    assert idx.size == 11
    idx.delete("a")
    keys, _ = idx.query(data[0], k=idx.size)
    assert "a" not in keys
    keys, _ = idx.query(data[1], k=idx.size)
    assert "a" not in keys


def test_make_index_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown index kind"):
        make_index("annoy")


def test_make_index_from_config():
    from repro.configs.mememo import smoke_config
    cfg = smoke_config()
    idx = make_index_from_config(cfg)
    from repro.core.interface import HNSW
    assert isinstance(idx, HNSW) and idx.M == cfg.M
    idx_ivf = make_index_from_config(cfg, kind="ivf", nlist=4)
    from repro.core.ivf import IVFVectorIndex
    assert isinstance(idx_ivf, IVFVectorIndex) and idx_ivf.nlist == 4


# ---------------------------------------------------------------------------
# HNSW mutation internals: second bulk_insert, incremental device sync
# ---------------------------------------------------------------------------
def test_hnsw_second_bulk_insert_appends():
    from repro.core.interface import HNSW
    data = make_corpus(300, 16, seed=1)
    more = make_corpus(40, 16, seed=2)
    idx = HNSW(distance_function="cosine", M=8, ef_construction=40,
               use_bulk_build=True)
    idx.bulk_insert([f"a{i}" for i in range(300)], data)
    idx.bulk_insert([f"b{i}" for i in range(40)], more)   # must not drop a*
    assert idx.size == 340
    k, _ = idx.query(data[11], k=1)
    assert k[0] == "a11"
    k, _ = idx.query(more[7], k=1)
    assert k[0] == "b7"


def test_hnsw_incremental_sync_matches_full_rebuild():
    """Dirty-row journal upload must be bit-for-bit identical to a
    from-scratch ``to_device_graph`` over the same host state."""
    idx, data = build("hnsw", n=250, seed=3)
    q = data[:4]
    idx.query(q, k=5)                        # residency: full first upload
    assert not idx._builder.journal          # journal drained by the sync
    new = make_corpus(6, 16, seed=4)
    for j, v in enumerate(new):
        idx.insert(f"n{j}", v)
    idx.delete("d17")
    idx.delete("d91")
    assert idx._builder.journal              # mutations journaled
    idx.query(q, k=5)                        # incremental sync
    dg_inc = idx._device_graph

    b = idx._builder
    dg_full = jhnsw.to_device_graph(
        b.graph_full_capacity(b.max_level_cap), idx._deleted)
    for name in ("vectors", "neighbors0", "upper", "levels", "entry",
                 "deleted"):
        a, b = getattr(dg_inc, name), getattr(dg_full, name)
        if name in ("neighbors0", "upper"):  # packed: compare the tables
            assert a.width == b.width, name
            a, b = a.table, b.table
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    assert dg_inc.max_level == dg_full.max_level
    ids_a, d_a = jhnsw.search_graph(dg_inc, q, k=5, ef=64)
    ids_b, d_b = jhnsw.search_graph(dg_full, q, k=5, ef=64)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b))


def test_hnsw_deleted_entry_point_still_searchable():
    idx, data = build("hnsw", n=120, seed=6)
    entry_key = idx._keys[int(idx._builder.entry)]
    idx.delete(entry_key)                    # tombstone the entry point
    keys, _ = idx.query(data[60], k=3)
    assert entry_key not in keys and keys[0] is not None


# ---------------------------------------------------------------------------
# shard substrate (DESIGN.md §8) — host-side pieces testable on one device;
# the mesh fan-out / cross-shard parity suite is tests/test_sharded.py
# ---------------------------------------------------------------------------
def test_shard_routing_deterministic_and_balanced():
    from repro.core.sharded import shard_of_key
    keys = [f"doc-{i}" for i in range(4000)]
    a = [shard_of_key(k, 8) for k in keys]
    assert a == [shard_of_key(k, 8) for k in keys]   # stable (not hash())
    counts = np.bincount(a, minlength=8)
    assert counts.sum() == 4000 and counts.max() < 700  # roughly balanced
    assert all(shard_of_key(k, 1) == 0 for k in keys[:10])


def test_sharded_rows_free_slot_bookkeeping():
    """Tombstoned slots are reused by later inserts routed to the same
    shard; compaction re-derives a dense layout."""
    from repro.core.sharded import ShardedRows, shard_of_key
    rows = ShardedRows(n_shards=4, metric="cosine", dim=8)
    data = np.random.default_rng(0).normal(size=(40, 8)).astype(np.float32)
    for i in range(40):
        rows.upsert(f"k{i}", data[i])
    assert rows.size == 40
    victim = "k7"
    s7, slot7 = rows.placement_of_row(rows.key2row[victim])
    rows.tombstone(victim)
    # next insert routed to the same shard claims the freed slot
    probe = next(f"n{j}" for j in range(1000)
                 if shard_of_key(f"n{j}", 4) == s7)
    rows.upsert(probe, data[0])
    assert rows.placement_of_row(rows.key2row[probe]) == (s7, slot7)
    stats = rows.shard_stats()
    assert sum(st["live"] for st in stats) == 40
    # upsert of an existing key frees its old slot too
    rows.upsert(probe, data[1])
    assert rows.size == 40
    rows.compact()
    assert rows.row_count == 40 and rows.size == 40
    assert all(st["free"] == 0 for st in rows.shard_stats())
    assert victim not in rows.key2row
    # regression: a pre-existing key repeated WITHIN one batch must free
    # its old slot exactly once — a double release would hand the same
    # slot to two rows and desync the slot tables from the alive mask
    rows.upsert_many(["k3", "k3"], data[:2])
    occupied = {(s, slot) for s in range(4)
                for slot, r in enumerate(rows._slots[s]) if r >= 0}
    assert len(occupied) == int(rows.alive.sum())
    for s in range(4):
        st = rows.shard_stats()[s]
        assert st["slots"] - st["free"] == st["live"]


def test_sharded_without_devices_raises_helpfully():
    """n_shards beyond the process's device count: mutations (host-side)
    work, the first device search raises with the XLA_FLAGS recipe."""
    idx = make_index("flat", dim=8, metric="cosine", n_shards=4)
    idx.bulk_insert(["a", "b"], np.eye(8, dtype=np.float32)[:2])
    assert idx.size == 2 and idx.shard_count == 4
    import jax
    if len(jax.devices()) >= 4:
        pytest.skip("process has enough devices to place 4 shards")
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        idx.query(np.ones(8, np.float32), k=1)


@pytest.mark.parametrize("kind", KINDS)
def test_single_shard_config_roundtrips(kind):
    """n_shards=1 (default) is the historical layout: shard_count reports
    it, config round-trips through export/load."""
    idx, data = build(kind, n=40)
    assert idx.shard_count == 1
    assert idx.config_dict().get("n_shards", 1) == 1
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "idx.npz")
        idx.export(p)
        idx2 = type(idx).load(p)
        assert idx2.shard_count == 1
        k1, _ = idx.query(data[3], k=3)
        k2, _ = idx2.query(data[3], k=3)
        assert k1 == k2


def test_tiered_query_counts_slow_tier_traffic():
    idx, data = build("tiered", n=200, seed=7)
    idx.query(data[5], k=3)
    stats = idx.stats
    assert stats.transactions > 0 and stats.rows_fetched > 0
    # mutation invalidates the fast tier; stats reset with the new store
    idx.delete("d5")
    keys, _ = idx.query(data[5], k=3)
    assert "d5" not in keys


# ---------------------------------------------------------------------------
# RAGPipeline over the protocol (acceptance: flat + hnsw via make_index)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["flat", "hnsw"])
def test_rag_pipeline_over_make_index(kind):
    from repro.data.corpus import BUILTIN_CORPUS
    from repro.serve.rag import RAGPipeline

    rag = RAGPipeline(index_kind=kind)
    rag.add_documents(BUILTIN_CORPUS)
    out = rag.answer("how does mememo prefetch from IndexedDB?", k=3)
    assert any(d.key.startswith("mememo") for d in out["docs"])
    assert "{{user}}" not in out["prompt"]
    # retract a personal document: it must never be retrieved again
    top = out["docs"][0].key
    rag.delete_document(top)
    out2 = rag.answer("how does mememo prefetch from IndexedDB?", k=3)
    assert all(d.key != top for d in out2["docs"])
    # live update: re-embedded text is retrieved under the same key
    rag.update_document("tpu-0", "mememo prefetches neighbors from indexeddb")
    out3 = rag.answer("how does mememo prefetch from IndexedDB?", k=2)
    assert any(d.key == "tpu-0" for d in out3["docs"])
