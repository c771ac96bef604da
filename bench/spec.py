"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its correctness limits are
``bench/limits/<cell>.json``; each metric is read by
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import json
from pathlib import Path


class SpecError(Exception):
    """A file the cell needs is missing or does not say what it must."""


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None


def load_cell(root: Path, workload: str):
    """(benchmark, cell, configuration, traffic, limits) of one cell."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no cell {workload!r} in BENCHMARK.json ({sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    return bench, cell, cfg, traffic, limits
