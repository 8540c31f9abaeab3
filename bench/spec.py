"""The benchmark's specification: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own and is found here by name:

    bench/configs/<config>.json     sizes, index settings, check limits
    bench/configs/<config>.py       the plain reference of its stored rows
    bench/traffic/<traffic>.json    parameters of the one general generator
    bench/metrics/<metric>.py       a reader that returns the metric or None

So a cell, a configuration, a traffic mix or a per-layer metric is added
by adding files and entries, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what} {name!r} is not a valid name")
    return name


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    spec = json.loads(path.read_text())
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in spec[sec]:
            name = check_name(entry["name"], sec)
            if name in seen:
                raise SpecError(f"{sec}: {name!r} appears twice")
            seen.add(name)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            raise SpecError(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better is {m['better']!r}")
    return spec


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    return json.loads(path.read_text())


class Cell:
    """One workload of BENCHMARK.json with everything it names resolved."""

    def __init__(self, spec: dict, name: str, root: Path = ROOT):
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                            f"known: {sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = {c["name"]: c for c in spec["configs"]}[
            check_name(self.workload["config"], "config")]
        self.config_name = cfg_entry["name"]
        self.config = load_json(root / cfg_entry["file"])
        self.reference = _load_module(
            (root / cfg_entry["file"]).with_suffix(".py"),
            f"bench_ref_{self.config_name}")
        traffic = check_name(self.workload["traffic"], "traffic")
        self.traffic = load_json(root / "bench" / "traffic" / f"{traffic}.json")
        self.end_to_end = [m for m in spec["end_to_end"] if reports(m, name)]
        self.per_layer = [m for m in spec["per_layer"] if reports(m, name)]
        self.readers = {m["name"]: _load_module(
            root / "bench" / "metrics" / f"{m['name']}.py",
            "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
            for m in self.end_to_end + self.per_layer}


def reports(metric: dict, workload: str) -> bool:
    """True where the metric is reported in this workload: every workload
    when the metric lists none."""
    cells = metric.get("workloads")
    return cells is None or workload in cells
