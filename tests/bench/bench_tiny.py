"""A tiny cell for CPU tests of the harness: the benchmark's code paths at
a size a test run can hold, with the fixture files in `data/`."""

from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

PEAKS = {"cpu": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}}
SEED = 2**31 + 77


def cell(family: str) -> SimpleNamespace:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        m.setdefault("workloads", []).append("tiny")
    return SimpleNamespace(
        name="tiny", entry={"config": f"tiny-{family}", "chips": 1}, spec=spec,
        model=json.loads((DATA / f"tiny-{family}.json").read_text()),
        traffic=json.loads((DATA / "tiny-traffic.json").read_text()),
        params=json.loads((DATA / "tiny-cell.json").read_text()))
