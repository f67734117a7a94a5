"""A benchmark root for CPU tests: a copy of ``bench/`` with a smoke-size
configuration, cells, traffic mixes and a kind of arrivals added as new
files, and a ``BENCHMARK.json`` that names them. Nothing of the copied
tree is edited."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = pathlib.Path(__file__).resolve().parent / "data"

# cell -> (traffic mix, the cell whose metrics it reports)
CELLS = {
    "smoke.chat": ("smoke_chat", "qwen3-1.7b.chat"),
    "smoke.backlog": ("smoke_backlog", "internlm2-1.8b.reasoning"),
    "smoke.bursty": ("smoke_bursty", "qwen3-1.7b.chat"),
}


def make_root(tmp: pathlib.Path, *, cells=tuple(CELLS)) -> pathlib.Path:
    root = pathlib.Path(tmp)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(DATA / "smoke_config.json", root / "bench/configs/smoke.json")
    for mix in ("smoke_chat", "smoke_backlog", "smoke_bursty"):
        shutil.copy(DATA / f"{mix}.json", root / f"bench/traffic/{mix}.json")
    shutil.copy(DATA / "smoke_bursty.py",
                root / "bench/traffic/arrivals/bursty.py")
    cell = json.loads((DATA / "smoke_cell.json").read_text())
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "smoke", "source": "test",
                             "file": "bench/configs/smoke.json",
                             "reduced": [], "why": "test"})
    for name in cells:
        (root / f"bench/cells/{name}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": name, "config": "smoke",
                                   "traffic": CELLS[name][0], "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c in cells
                               if CELLS[c][1] in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
