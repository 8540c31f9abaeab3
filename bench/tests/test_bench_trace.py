"""The trace reduction on a small trace recorded on a TPU v5e
(``data/trace_small.json``: the first two searches of each cell, cut from
a chip run's trace, instruction names shortened) and on a trace the
profiler records here on the CPU."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.devtrace import Trace  # noqa: E402

SMALL = json.loads((Path(__file__).parent / "data"
                    / "trace_small.json").read_text())


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"t_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


BEAM = reader("beam_search_roofline")
TOPK = reader("distance_topk_roofline")


def sweep_busy(events, lo, hi) -> float:
    """Busy time by a sweep over interval edges, for comparison."""
    edges = sorted([(max(s, lo), 1) for _, s, e in events if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in events
                      if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("cell", ["hnsw", "flat"])
def test_busy_is_the_union_of_op_intervals(cell):
    tr = Trace.from_json(SMALL[cell])
    lo, hi = tr.window()
    (dev, ops), = SMALL[cell]["ops"].items()
    assert tr.mean_busy_ns(lo, hi) == pytest.approx(sweep_busy(ops, lo, hi))
    idle = sum(b - a for a, b in tr.idle_gaps(dev, lo, hi))
    assert idle + tr.busy_ns(dev, lo, hi) == pytest.approx(hi - lo)


def test_recorded_values():
    hnsw = Trace.from_json(SMALL["hnsw"])
    lo, hi = hnsw.window()
    assert hi - lo == 8707280
    assert hnsw.mean_busy_ns(lo, hi) == 1349624.0
    flat = Trace.from_json(SMALL["flat"])
    lo, hi = flat.window()
    assert flat.mean_busy_ns(lo, hi) == 1614349.0


@pytest.mark.parametrize("cell,kernel,other", [("hnsw", BEAM, TOPK),
                                               ("flat", TOPK, BEAM)])
def test_kernel_time_by_name(cell, kernel, other):
    """One launch of the cell's kernel per search, and none of the other's."""
    tr = Trace.from_json(SMALL[cell])
    lo, hi = tr.window()
    (dev, ops), = SMALL[cell]["ops"].items()
    import re
    hits = [e - s for n, s, e in ops if re.search(kernel.SIGNATURE, n)]
    assert len(hits) == len(tr.spans("index.query_batch")) == 2
    assert tr.kernel_ns(kernel.SIGNATURE, lo, hi) == sum(hits)
    assert tr.kernel_ns(other.SIGNATURE, lo, hi) == 0
    assert tr.kernel_ns(kernel.SIGNATURE, lo, hi) == {
        "hnsw": 1156984.0, "flat": 1366552.0}[cell]


@pytest.mark.parametrize("cell", ["hnsw", "flat"])
def test_idle_gaps_go_to_the_innermost_host_span(cell):
    tr = Trace.from_json(SMALL[cell])
    lo, hi = tr.window()
    (dev, _), = SMALL[cell]["ops"].items()
    want: dict = {}
    for a, b in tr.idle_gaps(dev, lo, hi):
        mid = (a + b) / 2
        covering = [h for h in tr.host if h.start <= mid <= h.end]
        name = min(covering, key=lambda h: h.end - h.start).name
        want[name] = want.get(name, 0.0) + (b - a)
    got = tr.idle_by_host(lo, hi)
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])
    # the host was inside query_batch for most of the device's idle time
    assert max(got, key=got.get) == "index.query_batch"


def test_breakdown_labels_kernels():
    tr = Trace.from_json(SMALL["flat"])
    lo, hi = tr.window()
    top = tr.top_ops(lo, hi, {"distance_topk": TOPK.SIGNATURE})
    assert top[0][0] == "distance_topk"
    assert top[0][1] == 1366552.0


def test_cpu_trace_has_the_host_spans(tmp_path):
    """The loader reads a real profiler file: the benchmark's host spans
    come back by name (the CPU has no device planes)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("index.query_batch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = Trace.from_dir(str(tmp_path))
    lo, hi = tr.window()
    spans = tr.spans("index.query_batch")
    assert len(spans) == 2 and all(lo <= s.start <= s.end <= hi
                                   for s in spans)
    assert tr.ops == {}
    again = Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.window() == (lo, hi)
