#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration, a traffic mix and
its metrics; each is a file of its own under ``bench/`` (see spec.py).
A run:

  1. set-up (timed as ``setup_s``, from process start): draws the corpus
     and the queries from ``--seed``, builds the index through
     ``make_index`` and its bulk ingest, puts ``RetrievalEngine`` in front
     of it, and warms up every batch shape the traffic can produce, then
     runs the traffic for a second on queries of their own;
  2. the window: ``--seconds`` of the traffic through the engine, with no
     compile inside (the count is printed on stderr). ``--trace 1`` takes
     a profiler trace of the window and reports the per-layer metrics and
     a breakdown instead of the end-to-end ones;
  3. the check: once the device memory peak is read and the program's
     state is freed, every answer is compared with the plain reference
     (check.py), and each number compared is printed beside its limit.

The last line of stdout is one JSON object. Without a TPU, or with fewer
chips than the cell asks for, or outside the repository, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))

from bench import check, data, load  # noqa: E402
from bench.spec import Cell, SpecError, load_benchmark  # noqa: E402

RECALL_REQUESTS = 8192     # recall@10 is read on the stream's first 8,192
RANK_SAMPLE = 2048         # answers whose rank_gap is read, from the seed
WARM_SECONDS = 1.0         # traffic run in set-up, on queries of its own
TRACE_SECONDS = 10.0       # a traced run profiles this much of the window
COMPILE_CACHE = BENCH / ".cache" / "jax"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) else v
    return out


def use_compile_cache(jax) -> None:
    """JAX's persistent cache in a fixed directory of the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one; every program is kept, so only a
    checkout's first run compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Recorder:
    """Span around every ``query_batch`` the engine makes: host times,
    the rows sent (bucket) and the engine's count of real rows so far."""

    def __init__(self, query_batch, stats, spans: load.Spans):
        self.query_batch, self.stats, self.spans = query_batch, stats, spans
        self.calls: list[list] = []

    def __call__(self, queries, *a, **kw):
        before = self.stats.searched_queries
        s = time.perf_counter() - self.spans.t0
        with self.spans.span("index.query_batch"):
            out = self.query_batch(queries, *a, **kw)
        self.calls.append([s, time.perf_counter() - self.spans.t0,
                           len(queries), before])
        return out

    def real_rows(self) -> list[list]:
        """-> [start, end, bucket rows, real rows] per call."""
        befores = [c[3] for c in self.calls] + [self.stats.searched_queries]
        return [[s, e, b, befores[j + 1] - befores[j]]
                for j, (s, e, b, _) in enumerate(self.calls)]


class Setup:
    """Everything made before the window: data, index, engine."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 wrap_index=None):
        from repro.core import make_index

        cfg = cell.config
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.k = int(cfg["k"])
        self.corpus = data.corpus(seed, cfg["rows"], cfg["dim"],
                                  cfg["clusters"], cfg["center_scale"])
        noise = cell.traffic["query_noise"]
        self.queries = data.QueryStream(self.corpus, seed, data.QUERIES,
                                        noise)
        self.queries.ensure(load.planned_requests(cell.traffic, seconds))
        self.warm = data.QueryStream(self.corpus, seed, data.WARMUP, noise)
        self.index = make_index(cfg["index"]["kind"],
                                **cfg["index"]["params"])
        self.index.bulk_insert([f"d{i}" for i in range(cfg["rows"])],
                               self.corpus)
        self.query_batch = self.index.query_batch
        self.wrap_index = wrap_index

    def engine(self, spans: load.Spans):
        """A fresh engine (empty cache) whose index calls are recorded."""
        from repro.serve.retrieval import RetrievalEngine

        eng = RetrievalEngine(self.index, **self.cell.config["engine"])
        qb = self.query_batch
        if self.wrap_index is not None:
            qb = self.wrap_index(self, qb)
        rec = Recorder(qb, eng.stats, spans)
        self.index.query_batch = rec
        return eng, rec

    def warm_up(self) -> None:
        """Every bucket shape of the traffic, then a second of the
        traffic itself, on the warm-up stream."""
        spans = load.Spans(time.perf_counter(), annotate=False)
        eng, _ = self.engine(spans)
        mb = self.cell.config["engine"].get("max_batch", 128)
        shapes = load.batch_shapes(self.cell.traffic, mb)
        n = 0
        for b in shapes:
            for _ in range(3):
                eng.retrieve(self.warm.take(range(n, n + b)), k=self.k)
                n += b
        self.warm.ensure(n + load.planned_requests(self.cell.traffic,
                                                   WARM_SECONDS))
        offset = _Offset(self.warm, n)
        spans.t0 = time.perf_counter()
        load.run(self.cell.traffic, eng, offset, self.seed + 1,
                 WARM_SECONDS, self.k, spans)

    def free(self) -> None:
        self.index = self.query_batch = None
        gc.collect()


class _Offset:
    """A query stream read from ``start`` on."""

    def __init__(self, stream, start: int):
        self.stream, self.start = stream, start

    def __getitem__(self, i: int):
        return self.stream[self.start + i]


_TRACES: list[str] = []


def count_traces() -> int:
    """Number of jit traces (each new shape is one) so far in this
    process; the listener is registered once."""
    import jax.monitoring
    if not _TRACES:
        _TRACES.append("listening")
        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, dur, **kw: _TRACES.append(ev)
            if ev == "/jax/core/compile/jaxpr_trace_duration" else None)
    return len(_TRACES) - 1


class Window:
    """What one measured window produced."""

    def __init__(self, setup: Setup, trace: bool, trace_dir: str | None,
                 seconds: float):
        import jax

        spans = load.Spans(time.perf_counter(), annotate=trace)
        eng, rec = setup.engine(spans)
        before = eng.stats.as_dict()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        n_traces = count_traces()
        # what set-up made is left out of the window's garbage collections
        gc.collect()
        gc.freeze()
        pauses: list[float] = []
        started = [0.0]

        def timed_gc(phase, info):
            if phase == "start":
                started[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - started[0])
        gc.callbacks.append(timed_gc)
        spans.t0 = time.perf_counter()
        try:
            with spans.span("bench.window"):
                self.requests = load.run(setup.cell.traffic, eng,
                                         setup.queries, setup.seed, seconds,
                                         setup.k, spans)
        finally:
            gc.callbacks.remove(timed_gc)
            gc.unfreeze()
        self.gc_pauses = pauses
        self.compiles = count_traces() - n_traces
        if trace:
            jax.profiler.stop_trace()
        after = eng.stats.as_dict()
        self.stats = {k: after[k] - before[k] for k in before
                      if isinstance(after[k], int)}
        self.calls = rec.real_rows()
        self.ticks = len(spans.steps)
        setup.index.query_batch = setup.query_batch
        r = self.requests
        self.answers = {i: (list(a[0]), np.asarray(a[1]))
                        for i, a in enumerate(r.answers) if a is not None}
        self.attempted = len(r.answers)


def memory_peak(jax) -> int | None:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def reference_readings(setup: Setup, win: Window) -> tuple[dict, dict]:
    """-> (readings, recall inputs), from the plain reference."""
    cell = setup.cell
    stored = cell.reference.stored_rows(setup.corpus)
    sample = data.rng(setup.seed, data.SAMPLE).permutation(
        max(win.attempted, 1))[:RANK_SAMPLE]
    read = check.readings(cell.reference, stored, setup.queries,
                          win.answers, win.attempted, setup.k, sample)
    first = []
    for i in range(min(RECALL_REQUESTS, win.attempted)):
        if i not in win.answers:
            break
        first.append(i)
    recall = {"found": [], "truth": None}
    if first:
        rows = check.normalize32(setup.corpus)
        truth, _ = check.exact_topk(rows, setup.queries.take(first),
                                    setup.k)
        recall = {"truth": truth,
                  "found": [check.key_ids([k for k in win.answers[i][0]
                                           if k is not None], len(rows))
                            for i in first]}
    return read, recall


def breakdown(trace, labels: dict, lo: float, hi: float) -> dict:
    ops = trace.top_ops(lo, hi, labels)
    idle = sorted(trace.idle_by_host(lo, hi).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / 1e9] for n, v in idle[:10]]}


def main(argv=None, *, t_start: float | None = None,
         require_chip: bool = True, overrides: dict | None = None,
         wrap_index=None, out=None, root: Path = ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT / 'src' / 'repro'} is missing: run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    try:
        spec = load_benchmark(root)
        cell = Cell(spec, args.workload, root)
    except SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if overrides:
        cell.config = merge(cell.config, overrides.get("config", {}))
        cell.traffic = merge(cell.traffic, overrides.get("traffic", {}))

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    if devices[0].platform == "tpu":
        use_compile_cache(jax)
    sys.path.insert(0, str(ROOT / "src"))

    setup = Setup(cell, args.seed, args.seconds, wrap_index)
    setup.warm_up()
    setup_s = time.perf_counter() - t_start

    # a traced run profiles the first TRACE_SECONDS of the traffic, which
    # bounds the trace's size and the time to read it
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        win = Window(setup, bool(args.trace), trace_dir, seconds)
        peak = memory_peak(jax)
        trace = None
        if args.trace:
            from bench.devtrace import Trace
            trace = Trace.from_dir(trace_dir)
            if not trace.ops:        # no TPU planes: nothing to reduce
                trace = None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    setup.free()
    t_ref = time.perf_counter()
    read, recall = reference_readings(setup, win)
    t_ref = time.perf_counter() - t_ref
    correct, checks = check.verdict(read, cell.config["checks"])

    r = win.requests
    ok = np.array(sorted(win.answers), dtype=np.int64)
    # what a metric reader reads (bench/metrics/)
    ctx = SimpleNamespace(
        cell=cell, config=cell.config, traffic=cell.traffic, seconds=seconds,
        setup_s=setup_s, device_kind=devices[0].device_kind,
        latency_ms=(r.done[ok] - r.due[ok]) * 1e3,
        queue_ms=(r.start[ok] - r.due[ok]) * 1e3,
        done_in_window=int(np.sum(r.done[ok] <= seconds)),
        recall=recall, stats=win.stats, calls=win.calls, trace=trace)
    metrics = {}
    wanted = cell.per_layer if args.trace else cell.end_to_end
    for m in wanted:
        value = cell.readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": win.attempted,
              "failed": win.attempted - len(win.answers),
              "metrics": metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": peak}}
    if trace is not None:
        lo, hi = trace.window()
        result["device"]["busy_s"] = trace.mean_busy_ns(lo, hi) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        labels = {r.KERNEL: r.SIGNATURE for r in cell.readers.values()
                  if hasattr(r, "SIGNATURE")}
        result["breakdown"] = breakdown(trace, labels, lo, hi)
    result["checks"] = checks

    late = (r.start[ok] - r.due[ok]) * 1e3
    print(f"bench: {cell.name} seed {args.seed}: setup {setup_s:.3f} s, "
          f"{win.attempted} requests, {len(win.answers)} answered, "
          f"{win.ticks} engine ticks, compiles in window "
          f"{win.compiles}, reference {t_ref:.3f} s, gc pauses "
          f"{len(win.gc_pauses)} (longest "
          f"{max(win.gc_pauses, default=0.0):.4f} s, total "
          f"{sum(win.gc_pauses):.4f} s); due-to-dispatch p50 "
          f"{np.percentile(late, 50) if late.size else float('nan'):.3f} ms"
          f" p99 {np.percentile(late, 99) if late.size else float('nan'):.3f}"
          " ms", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
