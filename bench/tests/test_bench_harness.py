"""The benchmark's harness on the CPU: BENCHMARK.json against its
contract, every file it names resolved by name, the generators seeded,
and every cell run end to end through ``run.main`` at a tiny size with
the look for a chip left out."""
from __future__ import annotations

import io
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import data, load, run, spec  # noqa: E402
from bench.tests.tiny import CELLS, overrides  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for sec, keys in ENTRY_KEYS.items():
        for entry in BENCH[sec]:
            extra = {"workloads"} if sec in ("end_to_end",
                                             "per_layer") else set()
            assert keys <= set(entry) <= keys | extra, entry
            assert spec.NAME_RE.match(entry["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_by_name(name):
    cell = spec.Cell(spec.load_benchmark(ROOT), name, ROOT)
    assert cell.chips in (1, 4)
    assert callable(cell.reference.stored_rows)
    assert isinstance(cell.reference.EXACT, bool)
    assert set(cell.config["checks"]) >= {"unanswered", "malformed",
                                          "dist_err"}
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.readers[m["name"]].read)


NEW_CELL = {
    "workloads": [{
        "name": "minilm-1m-flat-int8.closed32",
        "config": "minilm-1m-flat-int8",
        "traffic": "closed32", "chips": 1, "why": "32 closed-loop clients"}],
    "per_layer": [{
        "name": "client.p99_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "client", "moves": "p95_ms",
        "workloads": ["minilm-1m-flat-int8.closed32"]}],
}
NEW_FILES = {
    "bench/traffic/closed32.json":
        '{"loop": "closed", "clients": 32, "query_noise": 0.15}\n',
    "bench/metrics/client.p99_ms.py":
        "import numpy as np\n\n\ndef read(ctx):\n"
        "    return float(np.percentile(ctx.latency_ms, 99))\n",
}


def test_a_cell_is_added_by_adding_entries(tmp_path):
    """A new traffic mix and a new per-layer metric are one new file each:
    adding them and their entries to BENCHMARK.json, with no edit to any
    file of bench/, makes a cell that resolves and runs end to end."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for rel, text in NEW_FILES.items():
        assert not (tmp_path / rel).exists()
        (tmp_path / rel).write_text(text)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for sec, entries in NEW_CELL.items():
        bench[sec] = bench[sec] + entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.Cell(spec.load_benchmark(tmp_path),
                     "minilm-1m-flat-int8.closed32", tmp_path)
    assert cell.traffic == {"loop": "closed", "clients": 32,
                            "query_noise": 0.15}
    assert [m["name"] for m in cell.per_layer] == ["client.p99_ms"]
    for trace in (0, 1):
        out = io.StringIO()
        rc = run.main(["--workload", cell.name, "--seed", "5", "--seconds",
                       "1", "--trace", str(trace)], require_chip=False,
                      overrides=overrides(cell), out=out, root=tmp_path)
        assert rc == 0
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        assert line["correct"] is True
        wanted = cell.per_layer if trace else cell.end_to_end
        assert set(line["metrics"]) == {m["name"] for m in wanted}


def test_unknown_workload_and_bad_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.Cell(spec.load_benchmark(ROOT), "no-such-cell", ROOT)
    for bad in ("a b", "a/b", "", "x" * 65, "µs"):
        with pytest.raises(spec.SpecError):
            spec.check_name(bad, "name")


def test_generators_are_deterministic_per_seed():
    seed = 2**31 + 12345
    a = data.corpus(seed, 300, 16, 4, 1.5)
    assert a.dtype == np.float32 and a.shape == (300, 16)
    assert np.array_equal(a, data.corpus(seed, 300, 16, 4, 1.5))
    assert not np.array_equal(a, data.corpus(seed + 1, 300, 16, 4, 1.5))
    q1 = data.QueryStream(a, seed, data.QUERIES, 0.15)
    q2 = data.QueryStream(a, seed, data.QUERIES, 0.15)
    q2.ensure(3 * data.CHUNK)
    assert np.array_equal(q1.take([0, 5, data.CHUNK + 7]),
                          q2.take([0, 5, data.CHUNK + 7]))
    w = data.QueryStream(a, seed, data.WARMUP, 0.15)
    assert not np.array_equal(q1[0], w[0])


def test_arrivals_share_one_multiset_of_gaps():
    a = data.poisson_arrivals(1, 400.0, 10.0)
    b = data.poisson_arrivals(2**33 + 5, 400.0, 10.0)
    assert len(a) == len(b) == 4000
    assert (np.diff(a) > 0).all() and 0 < a[0] and a[-1] < 10.0
    assert not np.array_equal(a, b)
    def gaps(t):      # in nanoseconds, as integers
        return np.rint(np.diff(np.concatenate([[0.0], t])) * 1e9)
    # the same gaps in another order (the one after the last may differ)
    assert np.mean(np.isin(gaps(a), gaps(b))) > 0.99
    assert np.array_equal(a, data.poisson_arrivals(1, 400.0, 10.0))


def test_batch_shapes():
    assert load.batch_shapes({"loop": "open"}, 128) == [
        1, 2, 4, 8, 16, 32, 64, 128]
    assert load.batch_shapes({"loop": "closed", "clients": 128}, 128) == [128]
    assert load.batch_shapes({"loop": "closed", "clients": 200}, 128) == [
        128]
    assert load.batch_shapes({"loop": "closed", "clients": 300}, 128) == [
        64, 128]


def test_no_chip_no_result(capsys):
    out = io.StringIO()
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
                  out=out)
    assert rc != 0 and out.getvalue() == ""


def test_outside_the_repository_no_result(tmp_path):
    """In a directory holding only BENCHMARK.json and bench/, the run
    exits non-zero and prints nothing."""
    import subprocess
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end(name, trace):
    cell = spec.Cell(spec.load_benchmark(ROOT), name, ROOT)
    out = io.StringIO()
    rc = run.main(["--workload", name, "--seed", str(2**31 + 7),
                   "--seconds", "1", "--trace", str(trace)],
                  require_chip=False, overrides=overrides(cell), out=out)
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = {m["name"] for m in (cell.per_layer if trace
                                 else cell.end_to_end)}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
        assert all(m["value"] > 0 for m in line["metrics"].values())
