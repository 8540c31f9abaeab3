"""The peaks table and the kernels' work counts."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import peaks, work  # noqa: E402


def test_v5e_peaks_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
    with pytest.raises(KeyError):
        peaks.least_seconds("cpu", 1.0, 1.0)


def test_least_seconds_takes_the_binding_bound():
    # 197 GFLOP alone is 1 ms; 819 MB alone is 1 ms
    assert peaks.least_seconds("TPU v5 lite", 197e9, 0) == pytest.approx(1e-3)
    assert peaks.least_seconds("TPU v5 lite", 0, 819e6) == pytest.approx(1e-3)
    assert peaks.least_seconds("TPU v5 lite", 197e9, 2 * 819e6) == \
        pytest.approx(2e-3)


def test_beam_search_work():
    # 3 queries x ef 64 x 2M=10 rows of 384 fp32
    flops, nbytes = work.beam_search(3, 64, 5, 384)
    assert flops == 3 * 64 * 10 * 2 * 384
    assert nbytes == 3 * 64 * 10 * 384 * 4
    flops8, nbytes8 = work.beam_search(3, 64, 5, 384, "int8")
    assert flops8 == flops and nbytes8 == 3 * 64 * 10 * (384 + 4)


def test_distance_topk_work():
    flops, nbytes = work.distance_topk(128, 1_000_000, 384, "int8")
    assert flops == 2 * 128 * 1_000_000 * 384
    assert nbytes == 1_000_000 * 384 + 4 * 1_000_000 + 128 * 384 * 4
    _, nbytes32 = work.distance_topk(1, 10, 8)
    assert nbytes32 == 10 * 8 * 4 + 8 * 4
