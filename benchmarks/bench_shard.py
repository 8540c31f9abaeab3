"""Mesh-sharded retrieval sweep: shard count x corpus size (DESIGN.md §8).

Rows:
  shard_S{S}_n{N}       per-query critical-path latency at S shards: one
                        shard's local exact scan over ceil(N/S) rows plus
                        the tree merge's per-shard critical path —
                        ceil(log2 S) sequential rounds, each a measured
                        compiled two-key pairwise merge (the exact
                        ``_merge_pair`` program every ppermute round
                        runs). derived: speedup vs S=1, ``merge_us``
                        (wall of the REAL full S-way compiled
                        ``hierarchical_topk`` program — reported raw, not
                        folded into speedup, because on an oversubscribed
                        CPU simulator it is dominated by scheduling S
                        device threads on ~2 cores), the host's
                        wall-clock for the real sharded dispatch
                        (``host_wall_us``), rows per device, and the
                        aggregate-capacity headroom.
  shard_hnsw_S{S}_n{N}  sharded HNSW segment-set sweep: wall per-query
                        latency of the one-dispatch stacked fan-out
                        (core/stacked.py) vs the per-child Python loop
                        (``loop_us``) — the dispatch-count win the
                        compiled path buys, visible in BENCH_smoke.json.

Methodology note: CI hosts have ~2 cores, so the wall-clock of 8
simulated shards oversubscribes and says nothing about mesh scaling —
the critical-path decomposition (local scan at N/S + rounds x pairwise
merge) is the projection that does, and ``host_wall_us`` / ``merge_us``
keep the raw measurements honest alongside it. On a pod-slice the
projections and the walls converge. Both merge numbers are measured
compiled programs, not proxies: ``merge_us`` is the full S-way
shard_map tree (ppermute rounds included) and the per-round term is the
identical pairwise keep-k kernel on one device.

The sharded path needs a multi-device mesh, so this suite spawns ONE
subprocess with ``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before jax
imports — a CPU simulation on every host, whose rows say
``platform=cpu-sim``; the child never contends for a chip the parent
holds (the same idiom as tests/test_distributed.py) and
sweeps shard counts inside it — each S builds its mesh over the first S
fake devices. Smoke mode shrinks N for CI; the full run measures the
acceptance shape (N=100k, S in 1..8).
"""
import json
import os
import subprocess
import sys
import textwrap

SMOKE = os.environ.get("REPRO_BENCH_SMOKE") == "1"
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD = """
    import json, time
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import make_index
    from repro.core.sharded import SHARD_AXIS, shard_mesh
    from repro.distributed.collectives import _merge_pair, hierarchical_topk
    from repro.data.synthetic import make_corpus

    ns = {ns}
    shard_counts = {shard_counts}
    dim, b, k, reps = {dim}, {b}, {k}, {reps}
    hnsw_n = {hnsw_n}

    def timed(fn, *args):
        fn(*args)                                   # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps

    def timed_host(fn, *args):
        fn(*args)                                   # warm any lazy state
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(*args)
        return (time.perf_counter() - t0) / reps

    def merge_fn(s):
        # the REAL cross-shard merge: the same compiled ppermute tree
        # reduction the fan-out paths run (collectives.topk_merge_axis)
        mesh = shard_mesh(s)
        f = shard_map(
            lambda d, i: hierarchical_topk(d[0], i[0], k, (SHARD_AXIS,),
                                           tie_break_ids=True,
                                           axis_sizes=(s,)),
            mesh=mesh,
            in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None)),
            out_specs=(P(None, None), P(None, None)), check_vma=False)
        return jax.jit(f), mesh

    out = []
    for n in ns:
        data = make_corpus(n, dim, seed=0)
        keys = [f"d{{i}}" for i in range(n)]
        rng = np.random.default_rng(1)
        q = (data[rng.integers(0, n, b)]
             + 0.1 * rng.normal(size=(b, dim)).astype(np.float32))
        base_us = None
        for s in shard_counts:
            # real sharded dispatch on this host (fan-out + merge)
            idx = make_index("flat", dim=dim, metric="cosine", n_shards=s)
            idx.bulk_insert(keys, data)
            wall = timed(lambda: idx.query_batch(q, k=k)[1])

            # critical path: ONE shard's local scan over ceil(n/s) rows...
            rows_per = -(-n // s)
            local = make_index("flat", dim=dim, metric="cosine")
            local.bulk_insert(keys[:rows_per], data[:rows_per])
            t_local = timed(lambda: local.query_batch(q, k=k)[1])
            # ...plus the merge's per-shard critical path: ceil(log2 s)
            # sequential rounds of the two-key pairwise keep-k — the
            # exact per-round program, timed compiled on ONE device so
            # core oversubscription in the simulator can't pollute it
            if s > 1:
                pair = jax.jit(lambda d1, i1, d2, i2:
                               _merge_pair(d1, i1, d2, i2, k, True))
                cd = np.sort(rng.random((2, b, k)).astype(np.float32), -1)
                ci = rng.permutation(2 * b * k).astype(np.int32)
                ci = ci.reshape(2, b, k)
                t_pair = timed(pair, cd[0], ci[0], cd[1], ci[1])
                rounds = (s - 1).bit_length()
                t_merge = rounds * t_pair
                # the REAL full s-way compiled tree, for the record
                mfn, mesh = merge_fn(s)
                md = np.sort(rng.random((s, b, k)).astype(np.float32), -1)
                mi = rng.permutation(s * b * k).astype(np.int32)
                mi = mi.reshape(s, b, k)
                spec = NamedSharding(mesh, P(SHARD_AXIS, None, None))
                t_full = timed(mfn, jax.device_put(jnp.asarray(md), spec),
                               jax.device_put(jnp.asarray(mi), spec))
            else:
                t_merge = t_full = 0.0

            crit_us = (t_local + t_merge) / b * 1e6
            if base_us is None:
                base_us = crit_us
            out.append({{"row": "flat", "s": s, "n": n, "us": crit_us,
                         "merge_us": t_full / b * 1e6,
                         "wall_us": wall / b * 1e6,
                         "speedup": base_us / crit_us,
                         "rows_per_dev": rows_per}})

        # sharded HNSW segment-set sweep: one-dispatch stacked fan-out
        # vs the per-child Python loop (the pre-compiled-path cost)
        hd = data[:hnsw_n]
        hq = q
        for s in shard_counts:
            idx = make_index("hnsw", metric="cosine", M=8,
                             ef_construction=40, ef_search=32, n_shards=s,
                             use_bulk_build=True)
            idx.bulk_insert(keys[:hnsw_n], hd)
            wall = timed_host(lambda: idx.query_batch(hq, k=k)[1])
            loop = (timed_host(
                        lambda: idx._query_batch_sharded_loop(hq, k, None)[1])
                    if s > 1 else wall)
            out.append({{"row": "hnsw", "s": s, "n": hnsw_n,
                         "us": wall / b * 1e6, "loop_us": loop / b * 1e6,
                         "speedup_vs_loop": loop / wall}})
    print("ROWS" + json.dumps(out))
"""


def run(rows: list):
    # batch 128: the fake-device collective program carries a ~ms fixed
    # launch fee (8 device threads on a 2-core CI host) that is pure
    # simulation artifact; a serving-sized batch amortizes it so
    # merge_us reflects per-query cost, not 1/b of a scheduling fee
    if SMOKE:
        ns, shard_counts, dim, b, k, reps = [20_000], [1, 2, 4, 8], 32, 128, 10, 3
        hnsw_n = 2_000
    else:
        ns, shard_counts, dim, b, k, reps = [100_000], [1, 2, 4, 8], 64, 128, 10, 3
        hnsw_n = 20_000
    code = textwrap.dedent(_CHILD.format(
        ns=ns, shard_counts=shard_counts, dim=dim, b=b, k=k, reps=reps,
        hnsw_n=hnsw_n))
    env = dict(os.environ)
    # a CPU simulation: the child never reaches for the chip, which the
    # parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"bench_shard child failed: {proc.stderr[-2000:]}")
    payload = next(line for line in proc.stdout.splitlines()
                   if line.startswith("ROWS"))
    for r in json.loads(payload[len("ROWS"):]):
        if r["row"] == "flat":
            rows.append((f"shard_S{r['s']}_n{r['n']}", r["us"],
                         f"platform=cpu-sim,"
                         f"speedup={r['speedup']:.2f}x,"
                         f"merge_us={r['merge_us']:.0f},"
                         f"host_wall_us={r['wall_us']:.0f},"
                         f"rows_per_dev={r['rows_per_dev']},"
                         f"capacity_headroom={r['s']}x"))
        else:
            rows.append((f"shard_hnsw_S{r['s']}_n{r['n']}", r["us"],
                         f"platform=cpu-sim,"
                         f"loop_us={r['loop_us']:.0f},"
                         f"speedup_vs_loop={r['speedup_vs_loop']:.2f}x,"
                         f"dispatches=1"))
