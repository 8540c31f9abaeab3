"""The comparison that decides ``correct`` fails where it must, at a size
the CPU holds: the control (the plain reference in the program's place
at bfloat16) and faults planted in the timed path each come out not
correct, while the program itself comes out correct. Each cell's data
and index are built once; every case drives a window of the cell's own
traffic through the engine and judges it as a run does."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, run, spec  # noqa: E402
from bench.control import control  # noqa: E402
from bench.tests.tiny import CELLS, overrides  # noqa: E402

SEED = 2**31 + 99


def altered_answer(setup, program):
    """One key of each batch's first answer swapped for another row's."""
    def qb(q, k=10, **kw):
        keys, d = program(q, k=k, **kw)
        keys = [list(row) for row in keys]
        i = int(keys[0][0][1:])
        keys[0][0] = f"d{(i + 1) % len(setup.corpus)}"
        return keys, d
    return qb


def half_left_out(setup, program):
    """Only the first half of each batch searched; the rest given the
    first half's answers."""
    def qb(q, k=10, **kw):
        h = max(len(q) // 2, 1)
        keys, d = program(q[:h], k=k, **kw)
        keys = [list(r) for r in keys]
        d = np.asarray(d)
        idx = [j % h for j in range(len(q))]
        return [keys[j] for j in idx], d[idx]
    return qb


def unanswered(setup, program):
    """A dispatch that raises: its requests come back with an error."""
    def qb(q, k=10, **kw):
        raise RuntimeError("planted fault")
    return qb


@pytest.fixture(scope="module", params=CELLS)
def built(request):
    cell = spec.Cell(spec.load_benchmark(ROOT), request.param, ROOT)
    over = overrides(cell)
    cell.config = run.merge(cell.config, over["config"])
    cell.traffic = run.merge(cell.traffic, over["traffic"])
    setup = run.Setup(cell, SEED, 1.0)
    setup.warm_up()
    return setup


def judge(setup, wrap):
    setup.wrap_index = wrap
    try:
        win = run.Window(setup, False, None, setup.seconds)
    finally:
        setup.wrap_index = None
    read, _ = run.reference_readings(setup, win)
    return check.verdict(read, setup.cell.config["checks"])


def test_program_is_correct(built):
    ok, checks = judge(built, None)
    assert ok, checks
    assert checks["dist_err"]["value"] < 1e-6


def test_control_is_not_correct(built):
    ok, checks = judge(built, control)
    assert not ok, checks
    assert checks["dist_err"]["value"] > 3 * 1e-6


@pytest.mark.parametrize("fault", [altered_answer, half_left_out,
                                   unanswered])
def test_planted_fault_is_not_correct(built, fault):
    ok, checks = judge(built, fault)
    assert not ok, checks
