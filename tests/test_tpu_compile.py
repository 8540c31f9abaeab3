"""Compile the main-path Pallas kernels for a TPU v5e, from the CPU.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached (``topologies.get_topology_desc``). These cases
build each kernel at the paper's widths (1M x 384 corpus, M=5 so 2M=10
layer-0 neighbors, ef 64 for queries and 20 for bulk ingest) and the
flash-decode shape the serving launcher runs; the beam kernel reads the
adjacency packed, as the resident graph stores it. The last cases compile
the whole HNSW search over the device graph of a host graph whose row
count is not a multiple of 8. The scan kernel's compiled op must also
keep the signature the benchmark's roofline reader looks for
(``bench/metrics/distance_topk_roofline.py``). A refusal here is what the
chip would raise. Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers each import
this file.
"""
import ast
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hnsw
from repro.kernels import ops
from repro.kernels.beam_search import beam_search_pallas
from repro.kernels.distance_topk import distance_topk_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.gather_distance import gather_distance_pallas
from repro.kernels.layout import PackedRows, device_capacity

ROOT = Path(__file__).resolve().parents[1]
N, D = 1_000_000, 384
N_ODD = N + 1                   # a host graph of N % 8 != 0 rows
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    # a described-chip compile can be cached but never read back here
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:      # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def _beam(b, ef, dtype=jnp.float32):
    def case(s):
        args = [s((N, D), dtype), PackedRows(s((N // 8, 128), jnp.int32), 10),
                s((b, D), jnp.float32), s((b,), jnp.int32),
                s((b,), jnp.float32)]
        if dtype == jnp.int8:
            args.append(s((N,), jnp.float32))

            def fn(v, nb, q, ep, epd, sc):
                return beam_search_pallas(v, nb, q, ep, epd, ef=ef,
                                          scales=sc, interpret=False)
        else:
            def fn(v, nb, q, ep, epd):
                return beam_search_pallas(v, nb, q, ep, epd, ef=ef,
                                          interpret=False)
        return fn, args
    return case


def _topk(dtype, k=10):
    def case(s):
        args = [s((N, D), dtype), s((128, D), jnp.float32)]
        if dtype == jnp.int8:
            args.append(s((N,), jnp.float32))

            def fn(db, q, sc):
                return distance_topk_pallas(db, q, k, scales=sc,
                                            interpret=False)
        else:
            def fn(db, q):
                return distance_topk_pallas(db, q, k, interpret=False)
        return fn, args
    return case


def _gather(s):
    def fn(v, q, ids):
        return gather_distance_pallas(v, q, ids, interpret=False)
    return fn, [s((N, D), jnp.float32), s((128, D), jnp.float32),
                s((128, 64), jnp.int32)]


def _flash(s):
    # launch/serve.py: llama3-8b smoke config (4 heads, 2 KV heads of
    # 16), 4 slots, max_len 128, f32 cache
    def fn(q, k, v, cur):
        return flash_decode_pallas(q, k, v, cur, interpret=False)
    return fn, [s((4, 4, 16), jnp.float32), s((4, 128, 2, 16), jnp.float32),
                s((4, 128, 2, 16), jnp.float32), s((4,), jnp.int32)]


CASES = {
    "distance_topk_f32": _topk(jnp.float32),
    # the benchmark's flat cell: int8 flat search returns the scan's own
    # top-10, with no over-fetch
    "distance_topk_int8": _topk(jnp.int8),
    # the one over-fetching caller of the scan, flat over the bf16 wire:
    # k=10 times the int8 rerank factor of 4
    "distance_topk_int8_k40": _topk(jnp.int8, k=40),
    "gather_distance": _gather,
    "beam_b1_ef64": _beam(1, 64),
    "beam_b8_ef64": _beam(8, 64),
    "beam_b128_ef64_int8": _beam(128, 64, jnp.int8),
    "beam_b1024_ef20_ingest": _beam(1024, 20),
    "flash_decode_serve": _flash,
}

# the name each case's kernel is given (``pallas_call(name=...)``): the
# compiled custom call carries it as its instruction name, which is how
# a device trace tells the kernels apart
KERNEL = {"distance_topk_f32": "distance_topk",
          "distance_topk_int8": "distance_topk",
          "distance_topk_int8_k40": "distance_topk",
          "gather_distance": "gather_distance",
          "beam_b1_ef64": "beam_search", "beam_b8_ef64": "beam_search",
          "beam_b128_ef64_int8": "beam_search",
          "beam_b1024_ef20_ingest": "beam_search",
          "flash_decode_serve": "flash_decode"}


def _roofline_signature() -> str:
    """The ``SIGNATURE`` regex of bench/metrics/distance_topk_roofline.py,
    read from its source (the benchmark's reader is not imported)."""
    tree = ast.parse((ROOT / "bench" / "metrics"
                      / "distance_topk_roofline.py").read_text())
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and getattr(node.targets[0], "id", None) == "SIGNATURE"):
            return ast.literal_eval(node.value)
    raise AssertionError("no SIGNATURE in distance_topk_roofline.py")


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    def shape(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    fn, args = CASES[name](shape)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    call = re.search(rf"%{KERNEL[name]}(\.\d+)? = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', text)
    assert call, name
    if KERNEL[name] == "distance_topk":
        # the benchmark's roofline reader finds the scan kernel by this
        # signature: two rank-3 outputs, f32 and s32
        assert re.search(_roofline_signature(), call.group(0)), call.group(0)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, (name, used)


@pytest.mark.parametrize("b,ef,dtype", [(1, 64, jnp.float32),
                                        (1024, 20, jnp.int8)])
def test_hnsw_search_odd_n_copies_no_table(one_chip, monkeypatch, b, ef,
                                           dtype):
    """The whole HNSW search (greedy descent + fused beam + tombstone
    filter) over the device graph of an N_ODD-row host graph, with the
    ops forced onto their TPU kernels. The graph's own layout (capacity
    rounded up, adjacency tables packed) must let every kernel view its
    tables without a copy, and the descent gather upper-layer rows
    without re-laying out a layer: the compile's temporaries stay under
    1/64 of the vectors (24 MB in fp32, 6 MB in int8), where one copy of
    a table would be 384 MB to 1.5 GB."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)

    cap = device_capacity(N_ODD)
    m, levels = 5, 8                    # the paper's M; log_M(1M) layers
    g = hnsw.DeviceGraph(
        vectors=s((cap, D), dtype),
        neighbors0=PackedRows(s((cap // 8, 128), jnp.int32), 2 * m),
        upper=PackedRows(s((levels * cap // 16, 128), jnp.int32), m),
        levels=s((cap,), jnp.int32),
        entry=s((), jnp.int32), deleted=s((cap,), jnp.bool_),
        max_level=levels, metric="cosine",
        scales=s((cap,), jnp.float32) if dtype == jnp.int8 else None)
    compiled = jax.jit(
        lambda g, q: hnsw.search_core(g, q, 10, ef)).lower(
            g, s((b, D), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    table = cap * D * jnp.dtype(dtype).itemsize
    assert mem.temp_size_in_bytes < table // 64, mem.temp_size_in_bytes
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
