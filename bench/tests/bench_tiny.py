"""Shared by the benchmark's CPU tests: a checkout-shaped directory
holding a toy configuration and toy mixes, with the real metric
readers and a copy of the real architecture families, so the harness
runs end to end on the CPU in seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELLS = {"tiny.open": ("tiny", "tiny_open"),
         "tiny.closed": ("tiny", "tiny_closed"),
         "tiny.steady": ("tiny", "tiny_steady"),
         "tiny16.open": ("tiny16", "tiny_open")}


def make_root(tmp: Path) -> Path:
    """A directory laid out like a checkout: BENCHMARK.json naming the
    toy cells, their files under bench/, bench/metrics linked to the
    real readers and bench/families a copy of the real families, to
    which a test may add its own."""
    root = tmp / "root"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    os.symlink(REPO / "bench" / "metrics", root / "bench" / "metrics")
    shutil.copytree(REPO / "bench" / "families", root / "bench" / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("tiny", "tiny16"):
        shutil.copy(DATA / f"{name}.json", root / "bench" / "configs")
    for mix in ("tiny_open", "tiny_closed", "tiny_steady"):
        shutil.copy(DATA / f"{mix}.json", root / "bench" / "traffic")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    open_cells = [c for c in CELLS if c.endswith(".open")]
    e2e = []
    for m in real["end_to_end"]:
        cells = (["tiny.closed", "tiny.steady"]
                 if m["name"] == "output_tok_s" else
                 list(CELLS) if m["name"] == "setup_s" else open_cells)
        e2e.append(dict(m, workloads=cells))
    bench = {
        "configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                    for n in ("tiny", "tiny16")],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1}
                      for w, (c, t) in CELLS.items()],
        "end_to_end": e2e,
        "per_layer": [dict(m, workloads=list(CELLS))
                      for m in real["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
