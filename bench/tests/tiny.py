"""A checkout-shaped directory holding tiny cells, for running the harness
on the CPU (``run.run(..., require_tpu=False)``)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

#: (cell prefix, configuration) of the tiny cells; each gets an open and a
#: closed loop
CONFIGS = (("tiny", "tiny_yi"), ("tiny.moe", "tiny_moe"))
CELLS = [f"{prefix}.{loop}" for prefix, _ in CONFIGS for loop in ("open", "closed")]


def make_root(tmp: Path, limit: float = 1.0) -> Path:
    """Cells ``tiny.open`` and ``tiny.closed`` on the program's yi-6b smoke
    model and ``tiny.moe.open`` and ``tiny.moe.closed`` on its granite-moe
    smoke model, with every metric of the real ``BENCHMARK.json`` reported
    in each and the served-token limit ``limit``."""
    (tmp / "src").symlink_to(REPO / "src")
    b = tmp / "bench"
    for sub in ("traffic", "limits"):
        (b / sub).mkdir(parents=True)
    shutil.copy(REPO / "bench" / "peaks.json", b / "peaks.json")
    for loop in ("open", "closed"):
        shutil.copy(DATA / f"tiny_{loop}.json", b / "traffic" / f"tiny_{loop}.json")
    for cell in CELLS:
        (b / "limits" / f"{cell}.json").write_text(json.dumps({"max_logit_gap": limit}))
    real = json.loads((REPO / "BENCHMARK.json").read_text())

    def everywhere(metrics):
        return [{k: v for k, v in m.items() if k != "workloads"} for m in metrics]

    (tmp / "BENCHMARK.json").write_text(json.dumps({
        **real,
        "configs": [{"name": name, "source": "tests", "file": str(DATA / f"{name}.json"),
                     "reduced": [], "why": "tests"} for _, name in CONFIGS],
        "workloads": [{"name": f"{prefix}.{loop}", "config": name, "traffic": f"tiny_{loop}",
                       "chips": 1, "why": "tests"}
                      for prefix, name in CONFIGS for loop in ("open", "closed")],
        "end_to_end": everywhere(real["end_to_end"]),
        "per_layer": everywhere(real["per_layer"]),
    }))
    return tmp
