"""Find the parts of a benchmark cell by name.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own under ``bench/``; ``BENCHMARK.json`` at the
root names them. A later cell, configuration, mix or metric is added as
new files and entries, with no edit to a file that is already here:

- ``bench/configs/<config>.json``  model sizes, as run; its ``arch`` names
  the architecture's module
- ``bench/archs/<arch>.py``        one architecture's layer equations: the
  program's ``ArchConfig``, the weight layout and the program's tree, the
  plain reference and its control, the operations of a step
- ``bench/traffic/<mix>.json``     traffic parameters for ``traffic/generate``
- ``bench/traffic/arrivals/<kind>.py``  when a mix's requests are due, how
  many a run offers, and whether the load is open-loop (``arrivals`` in
  the mix names the kind)
- ``bench/cells/<cell>.json``      deployment sizes and the cell's rate
- ``bench/end_to_end/<metric>.py`` reader of an end-to-end metric
- ``bench/layer_metrics/<metric>.py``  reader of a per-layer metric; a
  name with a ``.suffix`` (``idle_pct.chat``) uses the reader of its stem
- ``bench/peaks.json``             published peaks by ``device_kind``

A reader that counts a whole step's work (``mfu_pct``) takes the count
from the cell's architecture module; a kernel's roofline reader
(``paged_decode_roofline``, ``paged_prefill_roofline``) counts that one
kernel's work (``bench/kernels/``), so a new kernel comes with a reader
of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """One workload of ``BENCHMARK.json`` with every file it names."""

    name: str
    chips: int
    config: dict
    arch: object                   # the module bench/archs/<arch>
    traffic: dict
    arrivals: object               # the module bench/traffic/arrivals/<kind>
    cell: dict
    end_to_end: tuple[dict, ...]   # BENCHMARK.json entries of this cell
    per_layer: tuple[dict, ...]
    bench_dir: pathlib.Path


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries, workload) -> tuple[dict, ...]:
    """The metric entries that apply to ``workload``: those without a
    ``workloads`` list, and those that name it."""
    return tuple(e for e in entries
                 if workload in e.get("workloads", (workload,)))


def load_cell(workload: str, root: pathlib.Path | None = None) -> CellSpec:
    """Read ``BENCHMARK.json`` under ``root`` (the repository root) and the
    files it names for ``workload``. Raises ``KeyError`` for an unknown
    workload or a configuration without ``arch``, and
    ``FileNotFoundError`` for a missing file or architecture module."""
    root = pathlib.Path(root) if root is not None else BENCH_DIR.parent
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    w = by_name[workload]
    bench_dir = root / bench["paths"][0]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = root / configs[w["config"]]["file"]
    config = _read_json(config_file)
    if "arch" not in config:
        raise KeyError(f"{config_file} names no architecture (\"arch\")")
    if not (bench_dir / "archs" / f"{config['arch']}.py").exists():
        raise FileNotFoundError(
            f"{config_file} names the architecture {config['arch']!r}, and "
            f"there is no {bench_dir / 'archs' / config['arch']}.py")
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return CellSpec(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        arch=load_module(bench_dir, "archs", config["arch"]),
        traffic=traffic,
        arrivals=load_module(bench_dir, "traffic/arrivals",
                             traffic["arrivals"]),
        cell=_read_json(bench_dir / "cells" / f"{workload}.json"),
        end_to_end=_metrics(bench["end_to_end"], workload),
        per_layer=_metrics(bench["per_layer"], workload),
        bench_dir=bench_dir,
    )


def load_module(bench_dir: pathlib.Path, kind: str, stem: str):
    """The module ``<bench_dir>/<kind>/<stem>.py``, loaded from its file;
    ``FileNotFoundError`` when there is none."""
    path = pathlib.Path(bench_dir) / kind / f"{stem}.py"
    if not path.exists():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", f"{kind}_{stem}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: pathlib.Path, kind: str, name: str):
    """The ``read(ctx)`` function of metric ``name``: the module
    ``<bench_dir>/<kind>/<name>.py``, else the one named by the part of
    ``name`` before its first dot."""
    for stem in (name, name.split(".", 1)[0]):
        if (pathlib.Path(bench_dir) / kind / f"{stem}.py").exists():
            return load_module(bench_dir, kind, stem).read
    raise FileNotFoundError(f"no reader for {kind} metric {name!r} "
                            f"under {bench_dir / kind}")


def peaks_for(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = _read_json(pathlib.Path(bench_dir) / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
