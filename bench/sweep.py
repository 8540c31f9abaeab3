#!/usr/bin/env python3
"""Find an open-loop cell's knee: one build, then a window at each rate.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 2000,3000,4000

For each rate it prints one JSON line: requests offered and answered in
the window, latency percentiles, real rows per search, and how the
latency moved from the first fifth of the arrivals to the last (a queue
that grows through the window shows as a rising latency). The knee is the
highest rate whose queue does not grow; a cell runs at a fixed rate
below it, written into its traffic file. Run it once when a cell is
defined, on the chip; the benchmark's own runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run  # noqa: E402
from bench.spec import Cell, load_benchmark  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    run.use_compile_cache(jax)
    cell = Cell(load_benchmark(ROOT), args.workload, ROOT)
    if cell.traffic["loop"] != "open":
        print("sweep: only an open-loop cell has a rate", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    cell.traffic = dict(cell.traffic, rate_qps=max(rates))
    setup = run.Setup(cell, args.seed, args.seconds)
    setup.warm_up()
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for rate in rates:
        cell.traffic = dict(cell.traffic, rate_qps=rate)
        win = run.Window(setup, False, None, setup.seconds)
        r = win.requests
        ok = np.array(sorted(win.answers), dtype=np.int64)
        lat = (r.done[ok] - r.due[ok]) * 1e3
        fifth = max(len(ok) // 5, 1)
        print(json.dumps({
            "rate_qps": rate, "offered": win.attempted,
            "answered_in_window": int(np.sum(r.done[ok] <= args.seconds)),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_fifth_p50_ms": float(np.median(lat[:fifth])),
            "last_fifth_p50_ms": float(np.median(lat[-fifth:])),
            "rows_per_search": win.stats["searched_queries"]
            / max(win.stats["searches"], 1),
            "compiles_in_window": win.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
