"""The program's spans (core/dispatch.py) on the profiler's clock: a
profiler trace of RetrievalEngine ticks holds one ``engine.coalesce``
per tick and the index spans once per ``query_batch``, each inside its
``engine.dispatch``, under the exact names of ``SPANS``; with no
profiler running the results are the same. Flat int8 returns the scan's
own float32 top-k, so only HNSW writes ``index.rerank``."""
import dataclasses
import glob
import os
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import dispatch, make_index
from repro.data.synthetic import make_corpus
from repro.serve.retrieval import RetrievalEngine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.devtrace import HOST_SPANS  # noqa: E402

DIM, ROWS, MAX_BATCH = 16, 300, 8
# the index spans of one query_batch, in order, per kind
INDEX_SPANS = {"flat": ("index.scan", "index.fetch", "index.keys"),
               "hnsw": ("index.scan", "index.fetch", "index.rerank",
                        "index.keys")}


@dataclasses.dataclass
class Span:
    name: str
    start: float      # ns
    end: float        # ns


class Spans:
    """The host events of a profiler trace, by name."""

    def __init__(self, log_dir):
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        self.events = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for plane in ProfileData.from_file(path).planes
                       if plane.name.startswith("/host:")
                       for line in plane.lines for e in line.events]

    def spans(self, name):
        return sorted((e for e in self.events if e.name == name),
                      key=lambda e: e.start)


def build(kind):
    data = make_corpus(ROWS, DIM, seed=0)
    idx = make_index(kind, metric="cosine", dtype="int8", M=8,
                     ef_construction=60, ef_search=48)
    idx.bulk_insert([f"d{i}" for i in range(ROWS)], data)
    return idx, data


def queries(data, n, seed):
    rng = np.random.default_rng(seed)
    q = data[rng.integers(0, ROWS, n)] + 0.05 * rng.normal(
        size=(n, DIM)).astype(np.float32)
    q[1] = q[0]                     # a repeat: the tick's dedup fan-out
    return q


def ticks(eng, q, per_tick):
    """Submit ``per_tick`` queries and run one tick, until q is spent."""
    reqs = []
    for lo in range(0, len(q), per_tick):
        reqs += [eng.submit(row, k=5) for row in q[lo:lo + per_tick]]
        eng.step()
    return [(r.keys, r.dists) for r in reqs]


def traced(idx, q, per_tick, log_dir):
    """Run the ticks under the profiler -> (results, trace, number of
    query_batch calls)."""
    eng = RetrievalEngine(idx, max_batch=MAX_BATCH, cache_size=0)
    orig = idx.query_batch
    calls = []

    def counted(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    ticks(eng, q[:MAX_BATCH], MAX_BATCH)          # compile outside
    idx.query_batch = counted
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out = ticks(eng, q, per_tick)
    finally:
        jax.profiler.stop_trace()
        idx.query_batch = orig
    return out, Spans(log_dir), len(calls)


@pytest.mark.parametrize("kind", ["flat", "hnsw"])
def test_spans_nest_once_per_tick_and_call(kind, tmp_path):
    idx, data = build(kind)
    q = queries(data, 40, seed=1)
    per_tick = 20                   # 3 chunks of <= 8 rows per tick
    _, tr, calls = traced(idx, q, per_tick, str(tmp_path))
    n_ticks = len(q) // per_tick
    assert len(tr.spans("engine.coalesce")) == n_ticks
    disp = tr.spans("engine.dispatch")
    assert len(disp) == calls == n_ticks * 3
    # one fan-out per dispatch, and one for the tick with a repeat
    assert len(tr.spans("engine.fanout")) == len(disp) + 1
    for name in dispatch.SPANS:
        if name.startswith("index.") and name not in INDEX_SPANS[kind]:
            assert tr.spans(name) == [], name
    for name in INDEX_SPANS[kind]:
        spans = tr.spans(name)
        assert len(spans) == calls, name
        for s in spans:
            inside = [d for d in disp
                      if d.start <= s.start and s.end <= d.end]
            assert len(inside) == 1, name
    # within a call the index spans run in order and do not overlap
    for d in disp:
        inner = sorted((s for n in INDEX_SPANS[kind] for s in tr.spans(n)
                        if d.start <= s.start and s.end <= d.end),
                       key=lambda s: s.start)
        assert [s.name for s in inner] == list(INDEX_SPANS[kind])
        assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))


@pytest.mark.parametrize("kind", ["flat", "hnsw"])
def test_results_do_not_depend_on_the_profiler(kind, tmp_path):
    idx, data = build(kind)
    q = queries(data, 24, seed=2)
    on, _, _ = traced(idx, q, 12, str(tmp_path))
    off = ticks(RetrievalEngine(idx, max_batch=MAX_BATCH, cache_size=0),
                q, 12)
    keys, dists = idx.query_batch(q, k=5)
    assert [k for k, _ in off] == [k for k, _ in on] == keys
    np.testing.assert_array_equal(np.stack([d for _, d in off]),
                                  np.stack([d for _, d in on]))
    np.testing.assert_array_equal(np.stack([d for _, d in off]),
                                  np.asarray(dists))


def test_span_names_are_the_programs_own():
    """Distinct names, none of them one the benchmark writes around the
    program: a span never nests in one of its own name."""
    assert len(set(dispatch.SPANS)) == len(dispatch.SPANS)
    assert set(dispatch.SPANS).isdisjoint(HOST_SPANS)


@pytest.mark.parametrize("kind", ["flat", "hnsw"])
def test_the_trace_holds_no_span_outside_spans(kind, tmp_path):
    """Every name the program writes is listed in ``SPANS``, unchanged
    by the args it carries, and the listed names all appear but the
    index spans this kind does not write."""
    idx, data = build(kind)
    q = queries(data, 16, seed=3)
    _, tr, _ = traced(idx, q, 16, str(tmp_path))
    written = {e.name for e in tr.events
               if e.name.startswith(("engine.", "index."))}
    assert written == {n for n in dispatch.SPANS
                       if not n.startswith("index.")} | set(INDEX_SPANS[kind])
    assert set().union(*INDEX_SPANS.values()) == {
        n for n in dispatch.SPANS if n.startswith("index.")}
