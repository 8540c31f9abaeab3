#!/usr/bin/env python3
"""Bring-up smoke run of the retrieval main path on TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Everything runs in this one process, through the entry points a user
calls (``make_index``, ``RetrievalEngine``, ``repro.launch.serve.main``),
with the Pallas kernels compiled for the chip. Data is generated from
``--seed``. Every phase checks its answers against a plain host oracle
and raises on a mismatch; nothing is caught and carried past.

One chip, in order: the kernels against their oracles on a small input;
flat exact search over the paper's 1M x 384 corpus in fp32 and int8;
HNSW at the paper's settings (M=5, efConstruction=20, efSearch=64) built
by the device-resident bulk ingest and queried at B=1 and B=1024 through
``RetrievalEngine``; the beam kernel against its oracle on that graph;
deletion and update with the dirty-row device sync; an in-process RAG
serving run; and a check that the HNSW search, the flat search and the
decode step lower to TPU kernels.

Four chips: flat search over 4M x 384 rows in 4 shards against 1 shard on
the same rows (the results must be identical), and HNSW in 4 shards
against the flat oracle.

Lines starting ``info:`` are bring-up information (sizes, seconds), not
benchmark metrics. The last line of stdout is a JSON object naming the
device, printed only when every phase passed. Without a TPU, or outside
the repository, the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import functools
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DIM = 384               # MeMemo §5 corpus: all-MiniLM-L6-v2 embeddings
N_FLAT = 1_000_000
N_FLAT_SHARDED = 4_000_000
N_HNSW = 1_000_000
N_HNSW_SHARDED = 20_000          # sharded children build row by row on host
N_QUERIES = 1024
K = 10
HNSW_CFG = dict(M=5, ef_construction=20, ef_search=64)
# Recall@10 floor for HNSW against the exact answers, as a function of N.
# Derived on the CPU from this script's data and queries (seed 0) with
# the same settings and bulk ingest: recall@10 was 0.918 at N=20k, 0.770
# at 50k, 0.638 at 100k, 0.496 at 200k and 0.255 at 1M, about 0.1175
# lower per doubling of N (the 20k-1M fit; M=5 leaves 5-10 edges per
# node in a corpus of 64 isotropic clusters, where the 10 nearest
# neighbors are near-ties). The floor is that trend less 0.10, which also
# covers the chip's bf16 rounding in XLA distance code. Below 20k the
# floor stays at its 20k value.
def recall_floor(n: int) -> float:
    doublings = np.log2(max(n, 20_000) / 20_000)
    return max(0.05, 0.918 - 0.1175 * doublings - 0.10)


DIST_TOL = 1e-3


def info(msg: str) -> None:
    """Bring-up information: never a benchmark metric."""
    print(f"info: {msg}", flush=True)


def passed(msg: str) -> None:
    print(f"pass: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------- data
def corpus(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded clustered corpus [n, DIM] and N_QUERIES queries perturbed
    from corpus rows (drawn as in benchmarks/bench_query.py)."""
    from repro.data.synthetic import make_corpus
    data = make_corpus(n, DIM, seed=seed)
    rng = np.random.default_rng(seed + 1)
    queries = (data[rng.integers(0, n, N_QUERIES)]
               + 0.15 * rng.normal(size=(N_QUERIES, DIM)).astype(np.float32))
    return data, queries


def keys_of(n: int) -> list[str]:
    return [f"d{i}" for i in range(n)]


def ids_of(keys: list) -> np.ndarray:
    return np.array([[int(k[1:]) if k is not None else -1 for k in row]
                     for row in keys], np.int64)


def host_topk(rows: np.ndarray, q: np.ndarray, k: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """Exact cosine top-k on the host: rows and q already normalized."""
    d = 1.0 - q @ rows.T
    part = np.argpartition(d, k - 1, axis=1)[:, :k]
    pd = np.take_along_axis(d, part, 1)
    o = np.argsort(pd, axis=1, kind="stable")
    return np.take_along_axis(part, o, 1), np.take_along_axis(pd, o, 1)


def check_exact(name: str, keys: list, dists: np.ndarray,
                rows: np.ndarray, qn: np.ndarray) -> None:
    """Device answers against the host's exact top-k over the same rows:
    every returned distance within DIST_TOL of the host's, and every
    returned id a true top-k member up to ties (its distance no worse than
    the host's k-th plus DIST_TOL)."""
    ex_i, ex_d = host_topk(rows, qn, K)
    got = ids_of(keys)
    check((got >= 0).all(), f"{name}: a query returned fewer than {K} ids")
    host_d = 1.0 - np.einsum("bkd,bd->bk", rows[got], qn)
    err = float(np.abs(host_d - dists).max())
    check(err <= DIST_TOL, f"{name}: distance error {err} > {DIST_TOL}")
    worst = float((host_d - ex_d[:, -1:]).max())
    check(worst <= DIST_TOL,
          f"{name}: an id outside the exact top-{K} by {worst}")
    same = np.mean([len(set(g) & set(e)) / K for g, e in zip(got, ex_i)])
    passed(f"{name}: {len(qn)} queries against the host exact top-{K}: "
           f"max distance error {err:.2e}, id agreement {same:.4f} "
           f"(the rest are ties within {DIST_TOL})")


def timed(fn, reps: int = 3) -> tuple[float, float, object]:
    """(first call seconds, steady median ms, first result). Every call
    returns host data, so each ends when the device work has."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    steady = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        steady.append(time.perf_counter() - t0)
    return first, float(np.median(steady)) * 1e3, out


def recall_at_k(found: np.ndarray, truth: np.ndarray) -> float:
    return float(np.mean([len(set(f) & set(t)) / truth.shape[1]
                          for f, t in zip(found, truth)]))


# -------------------------------------------------------------- phases
def phase_kernels(seed: int) -> None:
    """The compiled kernels against their jnp oracles on a small input,
    the oracle at full f32 matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.core.codec import get_codec
    from repro.kernels import ref
    from repro.kernels.beam_search import beam_search_pallas
    from repro.kernels.distance_topk import distance_topk_pallas
    from repro.kernels.gather_distance import gather_distance_pallas

    rng = np.random.default_rng(seed)
    n, b = 4096, 16
    x = rng.normal(size=(n, DIM)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    q = rng.normal(size=(b, DIM)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    nbrs = rng.integers(0, n, (n, 10)).astype(np.int32)
    nbrs[rng.random((n, 10)) < 0.1] = -1
    ep = rng.integers(0, n, b).astype(np.int32)
    enc, scales = get_codec("int8").encode(x)
    xj, qj = jnp.asarray(x), jnp.asarray(q)
    with jax.default_matmul_precision("highest"):
        for name, rows, sc in (("fp32", xj, None),
                               ("int8", jnp.asarray(enc),
                                jnp.asarray(scales))):
            epd = ref.gather_distance_ref(rows, qj, jnp.asarray(ep)[:, None],
                                          scales=sc)[:, 0]
            args = (rows, jnp.asarray(nbrs), qj, jnp.asarray(ep), epd)
            ki, kd = beam_search_pallas(*args, ef=64, scales=sc)
            ri, rd = ref.beam_search_ref(*args, ef=64, scales=sc)
            agree = float(np.mean(np.asarray(ki) == np.asarray(ri)))
            derr = float(np.abs(np.asarray(kd) - np.asarray(rd)).max())
            check(agree >= 0.99 and derr <= 1e-4,
                  f"beam_search {name}: id agreement {agree}, "
                  f"distance error {derr}")
            ids = jnp.asarray(rng.integers(0, n, (b, 64)).astype(np.int32))
            gk = gather_distance_pallas(rows, qj, ids, scales=sc)
            gr = ref.gather_distance_ref(rows, qj, ids, scales=sc)
            gerr = float(np.abs(np.asarray(gk) - np.asarray(gr)).max())
            check(gerr <= 1e-4, f"gather_distance {name}: error {gerr}")
            pd, pi = distance_topk_pallas(rows, qj, K, scales=sc)
            neg, _ = jax.lax.top_k(-pd, K)
            td, ti = ref.distance_topk_ref(rows, qj, K, scales=sc)
            terr = float(np.abs(np.asarray(-neg) - np.asarray(td)).max())
            check(terr <= 1e-4, f"distance_topk {name}: error {terr}")
            passed(f"kernels {name} vs oracle: beam ids agree {agree:.4f} "
                   f"(dist err {derr:.1e}), gather err {gerr:.1e}, "
                   f"topk err {terr:.1e}")


def phase_flat(data: np.ndarray, queries: np.ndarray, dtype: str):
    """Flat exact search over the whole corpus; returns the index and its
    answers' ids [N_QUERIES, K] (the recall oracle for HNSW)."""
    from repro.core import make_index
    from repro.core.codec import get_codec
    from repro.core.hnsw_build import normalize_rows

    n = len(data)
    idx = make_index("flat", metric="cosine", dtype=dtype)
    t0 = time.perf_counter()
    idx.bulk_insert(keys_of(n), data)
    info(f"flat {dtype}: bulk_insert of {n} rows {time.perf_counter() - t0:.3f} s")
    first, ms, (keys, dists) = timed(lambda: idx.query_batch(queries, k=K))
    info(f"flat {dtype}: first query_batch (upload + compile) {first:.3f} s, "
         f"steady B={N_QUERIES} {ms:.3f} ms per call")
    rows = normalize_rows(data)
    if dtype != "fp32":
        codec = get_codec(dtype)
        rows = codec.decode(*codec.encode(rows))
    sub = slice(0, 32)
    check_exact(f"flat {dtype} N={n}", keys[sub], np.asarray(dists)[sub],
                rows, normalize_rows(queries[sub]))
    return idx, ids_of(keys)


def phase_hnsw(data: np.ndarray, queries: np.ndarray, truth: np.ndarray,
               n: int):
    """HNSW at the paper's settings via bulk ingest, queried through
    RetrievalEngine; returns the index and its B=1024 answers."""
    from repro.core import make_index
    from repro.serve.retrieval import RetrievalEngine

    idx = make_index("hnsw", metric="cosine", use_bulk_build=True,
                     **HNSW_CFG)
    t0 = time.perf_counter()
    idx.bulk_insert(keys_of(n), data[:n])
    info(f"hnsw: N={n} bulk_insert (device-resident ingest) "
         f"{time.perf_counter() - t0:.3f} s")
    eng = RetrievalEngine(idx, max_batch=N_QUERIES, cache_size=0)

    def one():
        r = eng.retrieve_one(queries[0], k=K)
        check(r.error is None, f"hnsw B=1: {r.error!r}")
        return r
    first, ms, _ = timed(one, reps=10)
    info(f"hnsw: B=1 first call (upload + compile) {first:.3f} s, "
         f"steady {ms:.3f} ms per query")

    def batch():
        reqs = eng.retrieve(queries, k=K)
        for r in reqs:
            check(r.error is None, f"hnsw B={N_QUERIES}: {r.error!r}")
        return [r.keys for r in reqs]
    first, ms, keys = timed(batch)
    info(f"hnsw: B={N_QUERIES} first call {first:.3f} s, steady "
         f"{ms:.3f} ms per call")
    rec = recall_at_k(ids_of(keys), truth)
    floor = recall_floor(n)
    check(rec >= floor, f"hnsw recall@{K} {rec:.4f} < floor {floor:.3f}")
    passed(f"hnsw N={n}: recall@{K} {rec:.4f} against the flat index "
           f"(floor {floor:.3f})")
    return idx, eng, keys


def phase_delete(idx, eng, keys: list, data: np.ndarray,
                 queries: np.ndarray, n: int, seed: int) -> None:
    """Delete 1,000 keys the queries just returned and move 100 other
    keys onto new vectors (other corpus rows, perturbed). After the
    dirty-row device sync the device graph must hold every moved key's
    new vector (equal to the host's row) and a tombstone on every deleted
    and every replaced row; the next search must return no deleted key,
    and must find moved keys by their new vectors at distance ~0."""
    import jax.numpy as jnp
    from repro.core.hnsw_build import normalize_rows

    order = list(dict.fromkeys(k for row in keys for k in row
                               if k is not None))
    deleted = order[:1000]
    check(len(deleted) == 1000, "fewer than 1000 distinct result keys")
    gone = set(deleted)
    moved = [k for k in keys_of(n)[::97] if k not in gone][:100]
    old_rows = [idx._key2id[k] for k in deleted + moved]
    rng = np.random.default_rng(seed + 2)
    fresh = (data[rng.integers(0, n, len(moved))]
             + 0.15 * rng.normal(size=(len(moved), DIM)).astype(np.float32))
    t0 = time.perf_counter()
    for k in deleted:
        idx.delete(k)
    for k, v in zip(moved, fresh):
        idx.update(k, v)
    info(f"delete 1000 + update 100 on the host: "
         f"{time.perf_counter() - t0:.3f} s")
    reqs = eng.retrieve(queries, k=K)
    for r in reqs:
        check(r.error is None, f"search after delete: {r.error!r}")
    leaked = {k for r in reqs for k in r.keys if k in gone}
    check(not leaked, f"{len(leaked)} deleted keys returned")

    # the synced device graph, read back row by row
    dg = idx._dg()
    new_rows = [idx._key2id[k] for k in moved]
    dev = np.asarray(dg.vectors[jnp.asarray(new_rows)])
    check(np.array_equal(dev, idx._builder.vectors[new_rows]),
          "moved rows on the device differ from the host's rows")
    err = float(np.abs(dev - normalize_rows(fresh)).max())
    check(err <= 1e-6, f"moved rows on the device are {err} from their "
          f"new vectors")
    dead = np.asarray(dg.deleted[jnp.asarray(old_rows)])
    check(dead.all(), f"{int((~dead).sum())} deleted or replaced rows "
          f"not tombstoned on the device")

    upd = eng.retrieve(fresh, k=K)
    hits = [float(r.dists[r.keys.index(k)]) for k, r in zip(moved, upd)
            if k in r.keys]
    # the graph search is approximate: a moved row may be missed, but a
    # row that is found must be found at its NEW vector. A moved key's
    # own new vector is its query's nearest row (distance 0), so it is
    # found at least as often as an average top-10 member: the limit is
    # the recall floor (chip runs found 97/100 at N=100k and 80/100 at
    # N=1M, where the limits are 55 and 16)
    limit = int(np.ceil(len(moved) * recall_floor(n)))
    check(len(hits) >= limit, f"only {len(hits)}/{len(moved)} moved keys "
          f"found by their new vectors (limit {limit})")
    check(max(hits) <= DIST_TOL, f"a moved key found at distance "
          f"{max(hits)}: the device holds its old vector")
    passed(f"deletion: 1000 deleted keys absent from {len(reqs)} results; "
           f"the device holds the {len(moved)} moved rows' new vectors "
           f"(max error {err:.1e}) and {len(old_rows)} tombstones; "
           f"{len(hits)}/{len(moved)} moved keys found at their new vectors "
           f"(limit {limit})")


def phase_hnsw_kernel(idx, queries: np.ndarray) -> None:
    """The fused beam kernel against its jnp oracle at the full N, on the
    index's own resident graph: 64 queries share one greedy descent,
    then the compiled kernel and ``ref.beam_search_ref`` (the same
    algorithm, gathers by XLA) each run the layer-0 beam at efSearch.
    Both at full f32 matmul precision."""
    import jax
    from repro.core import hnsw as jhnsw
    from repro.kernels import ref
    from repro.kernels.beam_search import beam_search_pallas

    dg = idx._dg()
    with jax.default_matmul_precision("highest"):
        q = jhnsw._prep_queries(dg, queries[:64])
        ep, epd = jax.jit(jhnsw.descend)(dg, q)
        args = (dg.vectors, dg.neighbors0, q, ep, epd)
        kw = dict(ef=HNSW_CFG["ef_search"], metric=dg.metric,
                  scales=dg.scales, expand_t=jhnsw.DEFAULT_EXPAND_T)
        ki, kd = beam_search_pallas(*args, **kw)
        ri, rd = jax.jit(functools.partial(ref.beam_search_ref, **kw))(
            *args)
    ki, kd, ri, rd = map(np.asarray, (ki, kd, ri, rd))
    agree = float(np.mean(ki == ri))
    live = (ki >= 0) & (ki == ri)
    derr = float(np.abs(kd[live] - rd[live]).max())
    check(agree >= 0.99 and derr <= 1e-4,
          f"hnsw kernel at N={dg.n}: ids agree {agree} with the oracle, "
          f"distance error {derr}")
    passed(f"hnsw beam kernel vs oracle at device N={dg.n}, 64 queries: "
           f"ids agree {agree:.4f}, distance error {derr:.1e}")


def phase_serve() -> None:
    from repro.launch import serve
    t0 = time.perf_counter()
    serve.main(["--rag", "--index", "hnsw", "--requests", "8",
                "--max-new", "8"])
    info(f"rag serve (8 requests, 8 new tokens): "
         f"{time.perf_counter() - t0:.3f} s including compile")
    passed("in-process RAG serve finished")


def phase_lowering(flat_idx, hnsw_idx) -> None:
    """The functions the earlier phases' indexes dispatch lower to TPU
    kernels (a Pallas ``tpu_custom_call``): the flat index's scan, the
    HNSW index's search with its own settings, and the decode step."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import hnsw as jhnsw
    from repro.models import transformer as tf

    q = jax.ShapeDtypeStruct((N_QUERIES, DIM), jnp.float32)
    flat = flat_idx._rows.pack()           # the FlatIndex its topk runs
    texts = {
        "hnsw search": jax.jit(
            lambda g, x: jhnsw.search_graph(
                g, x, k=K, ef=hnsw_idx.ef_search,
                beam_impl=hnsw_idx.beam_impl)).lower(
                    hnsw_idx._dg(), q).as_text(),
        "flat search": jax.jit(
            lambda v, sc, x: dataclasses.replace(
                flat, vectors=v, scales=sc).query(x, K)).lower(
                    flat.vectors, flat.scales, q).as_text(),
    }
    cfg = get_smoke_config("llama3-8b")
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    cache = tf.init_cache(cfg, 4, 128, jnp.float32)
    tok = jnp.zeros((4, 1), jnp.int32)
    texts["decode step"] = jax.jit(
        lambda p, t, c: tf.decode_step(p, cfg, t, c, dtype=jnp.float32)
    ).lower(params, tok, cache).as_text()
    for name, text in texts.items():
        check("tpu_custom_call" in text, f"{name}: no tpu_custom_call")
    passed("tpu_custom_call found in the HNSW index's search, the flat "
           "index's scan and the decode step")


def run_one_chip(seed: int, n_flat: int, n_hnsw: int) -> None:
    phase_kernels(seed)
    t0 = time.perf_counter()
    data, queries = corpus(n_flat, seed)
    info(f"corpus {n_flat} x {DIM} generated in "
         f"{time.perf_counter() - t0:.3f} s")
    flat, truth = phase_flat(data, queries, "fp32")
    phase_flat(data, queries, "int8")
    if n_hnsw < n_flat:            # the oracle must cover the same rows
        from repro.core import make_index
        sub = make_index("flat", metric="cosine")
        sub.bulk_insert(keys_of(n_hnsw), data[:n_hnsw])
        truth = ids_of(sub.query_batch(queries, k=K)[0])
        del sub
    idx, eng, keys = phase_hnsw(data, queries, truth, n_hnsw)
    phase_hnsw_kernel(idx, queries)
    phase_delete(idx, eng, keys, data, queries, n_hnsw, seed)
    phase_serve()
    phase_lowering(flat, idx)


def run_four_chips(seed: int, n_flat: int, n_hnsw: int) -> None:
    import jax
    from repro.core import make_index
    from repro.core.hnsw_build import normalize_rows

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, "
          f"found {len(jax.devices())}")
    t0 = time.perf_counter()
    data, queries = corpus(n_flat, seed)
    info(f"corpus {n_flat} x {DIM} generated in "
         f"{time.perf_counter() - t0:.3f} s")
    out = {}
    for s in (1, 4):
        idx = make_index("flat", metric="cosine", n_shards=s)
        t0 = time.perf_counter()
        idx.bulk_insert(keys_of(n_flat), data)
        info(f"flat S={s}: bulk_insert {time.perf_counter() - t0:.3f} s")
        first, ms, res = timed(lambda: idx.query_batch(queries, k=K))
        info(f"flat S={s}: first query_batch {first:.3f} s, steady "
             f"B={N_QUERIES} {ms:.3f} ms per call")
        out[s] = (res[0], np.asarray(res[1]))
        del idx
    check(out[1][0] == out[4][0], "4-shard flat ids differ from 1 shard")
    check(np.array_equal(out[1][1], out[4][1]),
          "4-shard flat distances differ from 1 shard")
    passed(f"flat N={n_flat}: 4 shards identical to 1 shard "
           f"({N_QUERIES} queries, ids and distances)")

    rows = normalize_rows(data[:n_hnsw])
    rng = np.random.default_rng(seed + 3)
    hq = (data[rng.integers(0, n_hnsw, N_QUERIES)]
          + 0.15 * rng.normal(size=(N_QUERIES, DIM)).astype(np.float32))
    truth, _ = host_topk(rows, normalize_rows(hq), K)
    idx = make_index("hnsw", metric="cosine", n_shards=4, **HNSW_CFG)
    t0 = time.perf_counter()
    idx.bulk_insert(keys_of(n_hnsw), data[:n_hnsw])
    info(f"hnsw S=4: N={n_hnsw} bulk_insert (host child builders) "
         f"{time.perf_counter() - t0:.3f} s")
    first, ms, (keys, _) = timed(lambda: idx.query_batch(hq, k=K))
    info(f"hnsw S=4: first query_batch {first:.3f} s, steady "
         f"B={N_QUERIES} {ms:.3f} ms per call")
    rec = recall_at_k(ids_of(keys), truth)
    floor = recall_floor(n_hnsw)
    check(rec >= floor, f"hnsw S=4 recall@{K} {rec:.4f} < floor "
          f"{floor:.3f}")
    passed(f"hnsw S=4 N={n_hnsw}: recall@{K} {rec:.4f} against the exact "
           f"oracle (floor {floor:.3f})")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hnsw-n", type=int, default=None,
                    help="HNSW rows (default: 1M on one chip, "
                         f"{N_HNSW_SHARDED} sharded)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run it from the repository: src/repro is not "
              f"beside {Path(__file__).name}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.utils import use_compile_cache

    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = "not installed"
    info("versions: " + ", ".join(f"{k} {v}" for k, v in versions.items()))
    devices = jax.devices()
    info(f"devices: {devices}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    cache = Path(use_compile_cache())
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    info(f"compile cache: {cache} ({entries} entries at start)")

    if args.chips == 4:
        run_four_chips(args.seed, N_FLAT_SHARDED,
                       args.hnsw_n or N_HNSW_SHARDED)
    else:
        run_one_chip(args.seed, N_FLAT, args.hnsw_n or N_HNSW)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
