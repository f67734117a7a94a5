"""A benchmark root for CPU tests: a copy of ``bench/`` with smoke-size
configurations, an architecture, cells, traffic mixes and a kind of
arrivals added as new files, and a ``BENCHMARK.json`` that names them.
Nothing of the copied tree is edited."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = pathlib.Path(__file__).resolve().parent / "data"

# configuration -> (its file in DATA, its architecture module's file)
CONFIGS = {
    "smoke": ("smoke_config.json", BENCH / "archs" / "dense_gqa.py"),
    "smoke-moe": ("smoke_moe_config.json", DATA / "smoke_moe.py"),
}

# cell -> (configuration, traffic mix, the cell whose metrics it reports)
CELLS = {
    "smoke.chat": ("smoke", "smoke_chat", "qwen3-1.7b.chat"),
    "smoke.backlog": ("smoke", "smoke_backlog", "internlm2-1.8b.reasoning"),
    "smoke.bursty": ("smoke", "smoke_bursty", "qwen3-1.7b.chat"),
    "smoke-moe.backlog": ("smoke-moe", "smoke_backlog",
                          "internlm2-1.8b.reasoning"),
}


def smoke_config(name: str) -> dict:
    return json.loads((DATA / CONFIGS[name][0]).read_text())


def smoke_arch(name: str):
    """The architecture module of smoke configuration ``name``."""
    from bench import spec

    path = CONFIGS[name][1]
    return spec.load_module(path.parents[1], path.parent.name, path.stem)


def make_root(tmp: pathlib.Path, *, cells=tuple(CELLS)) -> pathlib.Path:
    root = pathlib.Path(tmp)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name, (file, arch) in CONFIGS.items():
        shutil.copy(DATA / file, root / f"bench/configs/{name}.json")
        if not (root / "bench/archs" / arch.name).exists():
            shutil.copy(arch, root / "bench/archs" / arch.name)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for mix in ("smoke_chat", "smoke_backlog", "smoke_bursty"):
        shutil.copy(DATA / f"{mix}.json", root / f"bench/traffic/{mix}.json")
    shutil.copy(DATA / "smoke_bursty.py",
                root / "bench/traffic/arrivals/bursty.py")
    cell = json.loads((DATA / "smoke_cell.json").read_text())
    for name in cells:
        config, mix, _ = CELLS[name]
        (root / f"bench/cells/{name}.json").write_text(json.dumps(cell))
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c in cells
                               if CELLS[c][2] in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
