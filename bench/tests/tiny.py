"""The cells of BENCHMARK.json, and overrides that shrink a cell to a
size the CPU runs in seconds."""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def overrides(cell) -> dict:
    traffic = {}
    if cell.traffic["loop"] == "open":
        traffic["rate_qps"] = min(cell.traffic["rate_qps"], 200)
    else:
        traffic["clients"] = min(cell.traffic["clients"], 16)
    return {"config": {"rows": 2000}, "traffic": traffic}
