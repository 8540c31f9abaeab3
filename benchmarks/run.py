"""Benchmark harness — one module per paper table/figure + the roofline
table from the dry-run. Prints ``name,us_per_call,derived`` CSV; with
``--json PATH`` also writes the machine-readable trajectory file
(schema in benchmarks/README.md).

    PYTHONPATH=src python -m benchmarks.run [--only build,query,...]
        [--smoke] [--json BENCH_out.json]

``--smoke`` sets REPRO_BENCH_SMOKE=1: every suite that honors it shrinks
to a seconds-scale configuration — the perf-path canary CI runs via
``scripts/run_tests.sh --smoke``.
"""
import argparse
import json
import os
import sys
import time

SUITES = ["build", "query", "tiered", "rag", "serve", "store", "shard",
          "memory", "tenant", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configurations (sets REPRO_BENCH_SMOKE=1)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (see benchmarks/README.md)")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else set(SUITES)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    rows: list = []
    failures = 0
    print("name,us_per_call,derived")
    for suite in SUITES:
        if suite not in only:
            continue
        mod = __import__(f"benchmarks.bench_{suite}", fromlist=["run"])
        t0 = time.perf_counter()
        n_before = len(rows)
        try:
            mod.run(rows)
        except Exception as e:  # keep the harness going; report the failure
            failures += 1
            rows.append((f"{suite}_FAILED", 0, f"{type(e).__name__}:{e}"))
        for name, us, derived in rows[n_before:]:
            print(f"{name},{us:.1f},{derived}")
        sys.stdout.flush()
        print(f"# suite {suite} done in {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)

    if args.json:
        out_dir = os.path.dirname(args.json)
        if out_dir:                      # CI writes to bench-results/…
            os.makedirs(out_dir, exist_ok=True)
        payload = {
            "schema_version": 1,
            "smoke": bool(args.smoke),
            "suites": sorted(only & set(SUITES)),
            "rows": [{"name": name, "us_per_call": round(float(us), 1),
                      "derived": derived} for name, us, derived in rows],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
