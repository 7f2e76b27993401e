"""A copy of the benchmark's tree with every cell cut to a CPU test's size."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TRAINER: dict = {}
SEARCH = {"pop_size": 8, "n_generations": 3}
TRAFFIC = {"search_seeds": [1, 2], "reference_rows": 8, "wave_rows": 32, "pool_waves": 2,
           "trace_waves": 1}

# The four stacked islands over four chips, not yet proved on the chip
# (PERF.md, open questions): tests add the cell as a later PR would.
ISLANDS = {"name": "seeds.islands4", "config": "printed-mlp-seeds", "traffic": "stacked_islands",
           "chips": 4, "why": "4 stacked islands of the paper search, one island per chip"}


def tree(dst: Path) -> Path:
    """``dst`` holding BENCHMARK.json and bench/ with tiny configs and traffic."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for sub in ("configs", "references", "traffic", "limits", "metrics", "drivers"):
        shutil.copytree(ROOT / "bench" / sub, dst / "bench" / sub)
    for f in (dst / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["trainer"].update(TRAINER)
        f.write_text(json.dumps(cfg))
    for f in (dst / "bench" / "traffic").glob("*.json"):
        tr = json.loads(f.read_text())
        tr.update({k: v for k, v in TRAFFIC.items() if k in tr})
        if "search" in tr:
            tr["search"].update(SEARCH)
        f.write_text(json.dumps(tr))
    return dst


def add_cell(root: Path, cell: dict, like: str) -> None:
    """Add ``cell`` to ``root`` with the metrics and limits of the cell
    ``like``: a new limits file and entries in BENCHMARK.json, nothing
    else."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(cell)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    limits = root / "bench" / "limits"
    (limits / f"{cell['name']}.json").write_text((limits / f"{like}.json").read_text())
