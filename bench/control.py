#!/usr/bin/env python3
"""Readings of the program and of its control, on the chip, per seed.

    python3 bench/control.py --workload <name> --seeds a,b,c --seconds <s>

For each seed it builds the cell once, runs one window of the cell's
traffic through the program and one through the control (the plain
reference in the program's place at bfloat16, check.Control), and
prints one JSON line with both sets of readings. The limits in a
configuration's ``checks`` are set between the two: above the largest
reading of the program over a dozen seeds or more, below the smallest
of the control's. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, run  # noqa: E402
from bench.spec import Cell, load_benchmark  # noqa: E402


def control(setup, program):
    stored = setup.cell.reference.stored_rows(setup.corpus)
    return check.Control(program, setup.cell.reference, stored).query_batch


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    run.use_compile_cache(jax)
    cell = Cell(load_benchmark(ROOT), args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        setup = run.Setup(cell, seed, args.seconds)
        setup.warm_up()
        out = {"seed": seed}
        for side, wrap in (("program", None), ("control", control)):
            setup.wrap_index = wrap
            win = run.Window(setup, False, None, setup.seconds)
            read, _ = run.reference_readings(setup, win)
            out[side] = dict(read, attempted=win.attempted)
        print(json.dumps(out), flush=True)
        del setup, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
