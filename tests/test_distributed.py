"""Multi-device tests: spawned subprocesses set the fake-device XLA flag
BEFORE importing jax (the main pytest process must keep 1 CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

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


def test_sharded_flat_topk_exact():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_flat_topk
        from repro.kernels import ref
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        db = jax.random.normal(jax.random.PRNGKey(0), (640, 16))
        q = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        d, i = jax.jit(lambda a, b: sharded_flat_topk(mesh, a, b, 10,
                                                      metric="l2"))(db, q)
        de, ie = ref.distance_topk_ref(db, q, 10, metric="l2")
        assert np.allclose(np.sort(np.asarray(d)), np.sort(np.asarray(de)),
                           atol=1e-4)
        assert (np.sort(np.asarray(i)) == np.sort(np.asarray(ie))).all()
        print("OK")
    """)
    assert "OK" in out


def test_sharded_flat_topk_awkward_n():
    """Regression: N not a multiple of the shard count used to silently
    drop the trailing ``N mod S`` rows (``n // n_shards`` truncation).
    The DB is now padded with sentinel rows whose ids are masked out of
    the merge — results must be exact at awkward N, including when the
    true top-k lives in the truncated tail and when N < n_shards."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_flat_topk
        from repro.kernels import ref
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        # 637 = 8 * 79 + 5: five tail rows used to vanish from the search
        db = jax.random.normal(jax.random.PRNGKey(0), (637, 16))
        q = db[-3:] + 0.001          # true neighbors ARE the tail rows
        d, i = jax.jit(lambda a, b: sharded_flat_topk(mesh, a, b, 10,
                                                      metric="l2"))(db, q)
        de, ie = ref.distance_topk_ref(db, q, 10, metric="l2")
        assert (np.sort(np.asarray(i)) == np.sort(np.asarray(ie))).all(), \\
            "tail rows still dropped"
        assert np.allclose(np.sort(np.asarray(d)), np.sort(np.asarray(de)),
                           atol=1e-4)
        assert np.asarray(i)[0, 0] == 634       # the tail row itself wins
        # degenerate: fewer rows than shards (every shard padded)
        db2 = jax.random.normal(jax.random.PRNGKey(2), (5, 16))
        d2, i2 = jax.jit(lambda a, b: sharded_flat_topk(
            mesh, a, b, 3, metric="l2"))(db2, db2[:2])
        de2, ie2 = ref.distance_topk_ref(db2, db2[:2], 3, metric="l2")
        assert (np.sort(np.asarray(i2)) == np.sort(np.asarray(ie2))).all()
        print("OK")
    """)
    assert "OK" in out


def test_sharded_topk_bf16_wire_recall():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import sharded_flat_topk
        from repro.kernels import ref
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        db = jax.random.normal(jax.random.PRNGKey(0), (4096, 32))
        db = db / jnp.linalg.norm(db, axis=1, keepdims=True)
        q = db[:8] + 0.01
        d, i = jax.jit(lambda a, b: sharded_flat_topk(
            mesh, a.astype(jnp.bfloat16), b, 10, wire_bf16=True))(db, q)
        de, ie = ref.distance_topk_ref(db, q, 10)
        hits = sum(len(set(np.asarray(i)[r]) & set(np.asarray(ie)[r]))
                   for r in range(8))
        assert hits >= 8 * 9, hits          # >=90% recall through bf16 wire
        print("OK")
    """)
    assert "OK" in out


# shared by the tree-merge parity tests: run hierarchical_topk under
# shard_map on the first ``s`` fake devices, tree path (static axis_sizes)
# or all-gather oracle (axis_sizes=None), optionally with the bf16 wire
_MERGE = """
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.sharded import SHARD_AXIS, shard_mesh
from repro.distributed.collectives import hierarchical_topk

def merge(s, d, i, k, tree, wire=False):
    mesh = shard_mesh(s)
    f = jax.jit(shard_map(
        lambda dd, ii: hierarchical_topk(
            dd[0], ii[0], k, (SHARD_AXIS,), wire_bf16=wire,
            tie_break_ids=True, axis_sizes=(s,) if tree else None),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None, None),) * 2,
        out_specs=(P(None, None), P(None, None)), check_vma=False))
    spec = NamedSharding(mesh, P(SHARD_AXIS, None, None))
    dd, ii = f(jax.device_put(jnp.asarray(d), spec),
               jax.device_put(jnp.asarray(i), spec))
    return np.asarray(dd), np.asarray(ii)
"""


def test_tree_merge_matches_allgather_oracle():
    """Bitwise parity of the ppermute tree reduction against the
    all-gather oracle at S in {2, 3, 4, 8} (non-power-of-two included),
    under heavy distance ties: the two-key (dist, id) sort must make
    both paths deterministic, identical to each other, and identical to
    a host lexsort ground truth (ties resolve to the smallest id)."""
    out = run_sub(prelude=_MERGE, code="""
        rng = np.random.default_rng(0)
        k, b = 8, 5
        for s in (2, 3, 4, 8):
            # integer distances from a 6-value alphabet: maximal tie
            # pressure across shards, every value exact in bf16 too
            d = np.sort(rng.integers(0, 6, (s, b, k)), -1).astype(np.float32)
            i = rng.permutation(s * b * k).astype(np.int32).reshape(s, b, k)
            td, ti = merge(s, d, i, k, True)
            od, oi = merge(s, d, i, k, False)
            assert (td == od).all() and (ti == oi).all(), s
            td2, ti2 = merge(s, d, i, k, True)     # deterministic re-run
            assert (td == td2).all() and (ti == ti2).all(), s
            dd = d.transpose(1, 0, 2).reshape(b, -1)
            ii = i.transpose(1, 0, 2).reshape(b, -1)
            for r in range(b):
                order = np.lexsort((ii[r], dd[r]))[:k]
                assert (ti[r] == ii[r][order]).all(), (s, r)
                assert (td[r] == dd[r][order]).all(), (s, r)
        print("OK")
    """)
    assert "OK" in out


def test_tree_merge_bf16_wire_parity():
    """The bf16 wire halves the per-round distance payload; with
    bf16-exact inputs the tree must stay bitwise identical to the
    oracle at the same wire precision AND to the fp32-wire result."""
    out = run_sub(prelude=_MERGE, code="""
        rng = np.random.default_rng(1)
        k, b = 6, 4
        for s in (3, 8):
            d = np.sort(rng.integers(0, 5, (s, b, k)), -1).astype(np.float32)
            i = rng.permutation(s * b * k).astype(np.int32).reshape(s, b, k)
            td, ti = merge(s, d, i, k, True, wire=True)
            od, oi = merge(s, d, i, k, False, wire=True)
            assert (td == od).all() and (ti == oi).all(), s
            fd, fi = merge(s, d, i, k, True, wire=False)
            assert (td == fd).all() and (ti == fi).all(), s
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_accuracy():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed.collectives import compressed_psum
        mesh = jax.make_mesh((8,), ("x",))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 1000))
        f = shard_map(lambda s: compressed_psum(s[0], "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(None), check_vma=False)
        got, want = f(x), jnp.sum(x, axis=0)
        rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        assert rel < 0.03, rel              # int8 quantisation error bound
        print("OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save under a (4,2) mesh; restore + reshard under (2,4) — elastic."""
    out = run_sub("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.train.checkpoint import CheckpointManager
        from repro.distributed.sharding import axis_rules, named_sharding
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        mesh_b = jax.make_mesh((2, 4), ("data", "model"))
        state = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        axes = {"w": ("batch", "mlp")}
        with tempfile.TemporaryDirectory() as td:
            ck = CheckpointManager(td)
            with axis_rules(mesh_a):
                placed = jax.device_put(state["w"],
                                        named_sharding((8, 8), "batch", "mlp"))
            ck.save(1, {"w": placed})
            got, _ = ck.restore_sharded(state, axes, mesh_b)
            assert np.array_equal(np.asarray(got["w"]),
                                  np.asarray(state["w"]))
            shard_shapes = {s.data.shape for s in got["w"].addressable_shards}
            assert shard_shapes == {(4, 2)}, shard_shapes   # (2,4) mesh layout
        print("OK")
    """)
    assert "OK" in out


def test_production_mesh_requires_512():
    out = run_sub("""
        import jax
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh(multi_pod=False)
        assert dict(m1.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK")
    """, devices=512)
    assert "OK" in out
