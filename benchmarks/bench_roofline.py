"""Roofline table from the dry-run artifacts (launch/dryrun.py output),
plus one MEASURED row for the cross-shard top-k merge.

Reads dryrun_pod_baseline.json / dryrun_tuned_both.json if present; cells
can be (re)generated with:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --preset tuned \
        --out dryrun_tuned_both.json

The merge row times the compiled ppermute tree reduction
(collectives.topk_merge_axis) at S=8 on fake CPU devices, in a child
process pinned to ``JAX_PLATFORMS=cpu`` (a CPU simulation, labelled
``platform=cpu-sim``; a failed child fails the suite) and derives
the wire traffic per round — ceil(log2 S) rounds of B*k*(4+4) bytes per
shard (f32 dist + i32 id; bf16 wire halves the dist half) — against the
achieved effective bandwidth, with the host-python merge the tree
replaces alongside for contrast. The point the row makes: the merge is
BANDWIDTH-bound (bytes on the interconnect), not HOST-bound (Python
concat + argsort per batch), and per-hop traffic is k-sized, not
S*k-sized.
"""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")

_MERGE_CHILD = """
    import json, time
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.sharded import SHARD_AXIS, shard_mesh
    from repro.distributed.collectives import hierarchical_topk

    s, b, k, reps = 8, 64, 16, 20
    mesh = shard_mesh(s)
    fn = jax.jit(shard_map(
        lambda d, i: hierarchical_topk(d[0], i[0], k, (SHARD_AXIS,),
                                       tie_break_ids=True, axis_sizes=(s,)),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS, None, None)),
        out_specs=(P(None, None), P(None, None)), check_vma=False))
    rng = np.random.default_rng(0)
    d = np.sort(rng.random((s, b, k)).astype(np.float32), -1)
    i = rng.permutation(s * b * k).astype(np.int32).reshape(s, b, k)
    spec = NamedSharding(mesh, P(SHARD_AXIS, None, None))
    dj, ij = jax.device_put(jnp.asarray(d), spec), jax.device_put(
        jnp.asarray(i), spec)
    jax.block_until_ready(fn(dj, ij))            # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(dj, ij))
    t_tree = (time.perf_counter() - t0) / reps

    def host_merge():                            # what the tree replaced
        dd = d.transpose(1, 0, 2).reshape(b, s * k)
        ii = i.transpose(1, 0, 2).reshape(b, s * k)
        j = np.argsort(dd, axis=1, kind="stable")[:, :k]
        return np.take_along_axis(dd, j, 1), np.take_along_axis(ii, j, 1)

    host_merge()
    t0 = time.perf_counter()
    for _ in range(reps):
        host_merge()
    t_host = (time.perf_counter() - t0) / reps

    rounds = (s - 1).bit_length()
    wire_round = b * k * (4 + 4)                 # f32 dist + i32 id, per shard
    total_bytes = s * rounds * wire_round
    allgather = b * (s - 1) * k * (4 + 4)        # the traffic the tree avoids
    print("ROW" + json.dumps({"s": s, "b": b, "k": k,
                              "t_tree_us": t_tree * 1e6,
                              "t_host_us": t_host * 1e6,
                              "rounds": rounds,
                              "wire_kb_round": wire_round / 1024,
                              "allgather_kb": allgather / 1024,
                              "gbps": total_bytes / t_tree / 1e9}))
"""


def run(rows: list):
    _merge_row(rows)
    for name in ("dryrun_pod_baseline.json", "dryrun_tuned_both.json"):
        path = os.path.join(ROOT, name)
        if not os.path.exists(path):
            rows.append((f"roofline_{name}", 0, "missing: run launch.dryrun"))
            continue
        cells = json.load(open(path))
        ok = [c for c in cells if c.get("status") == "ok"]
        tag = "baseline" if "baseline" in name else "tuned"
        for c in ok:
            step = max(c["t_compute_s"], c["t_memory_s"], c["t_collective_s"])
            rows.append((
                f"roofline_{tag}_{c['arch']}_{c['shape']}_{c['mesh']}",
                step * 1e6,
                f"bottleneck={c['bottleneck']},frac={c['roofline_fraction']:.2f},"
                f"useful={c['useful_ratio']:.2f},fits={c['fits_hbm']}"))


def _merge_row(rows: list):
    """Measured cross-shard merge roofline row (see module docstring)."""
    env = dict(os.environ)
    # a CPU simulation: the child never reaches for the chip, which the
    # parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MERGE_CHILD)],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"roofline merge child failed: {proc.stderr[-2000:]}")
    payload = next(line for line in proc.stdout.splitlines()
                   if line.startswith("ROW"))
    r = json.loads(payload[len("ROW"):])
    # on real interconnect the merge is bandwidth-bound (the point of the
    # k-sized per-hop traffic); on fake CPU devices the collective launch
    # fee dominates and we say so instead of faking the label
    bound = ("bandwidth" if r["t_tree_us"] <= r["t_host_us"]
             else "dispatch(cpu-sim)")
    rows.append((
        f"roofline_merge_S{r['s']}", r["t_tree_us"],
        f"platform=cpu-sim,rounds={r['rounds']},wire_kb_round={r['wire_kb_round']:.0f},"
        f"allgather_kb={r['allgather_kb']:.0f},"
        f"achieved_gbps={r['gbps']:.2f},host_merge_us={r['t_host_us']:.0f},"
        f"bound={bound}"))
