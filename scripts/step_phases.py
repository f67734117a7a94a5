"""Split a traced serving window by the engine's step phases.

    python3 scripts/step_phases.py --workload <cell> --seed <n> --seconds <s>
        [--root DIR] [--keep FILE]
    python3 scripts/step_phases.py --xplane FILE --chunk-size N

The first form runs on a TPU, from the root of a checkout: it serves one
window of a benchmark cell (``BENCHMARK.json`` under ``--root``, the
checkout by default) through ``bench.harness`` with the device profiler
on, as ``bench/run.py --trace 1`` does, and with the engine's tracer
enabled, so that the engine's step phases land in the same trace as host
events ``engine.<phase>`` with the step's batch as event stats (DESIGN.md
§8). ``--keep`` copies the trace's ``.xplane.pb`` there. The second form
reduces a kept trace over the span of its device operations. Either
prints one JSON line:

- ``idle_pct``, ``host_idle_pct``: the device's idle share of the window,
  and the part of it in which the host is inside a phase span other than
  ``host_sync`` (the wait for the device): the idle time that host work,
  not the device, leaves;
- ``idle_ms``: idle time inside each phase, ``<kind>/<phase>``, and
  outside every span (``outside``); ``commit`` counts with the step it
  commits, ``admit`` and ``draft`` with the step that follows them, and
  ``step`` is the step's own host work between its phases;
  ``idle_ms_per_step`` divides each phase's by the window's steps of
  its kind;
- ``programs``: runs and mean device time of each jitted step program,
  from the ``XLA Modules`` line of the device's plane;
- ``chunk_fill_pct``: live prompt rows (the ``chunk_tokens`` stat) of
  the window's steps that carry a chunk, over their count times the chunk
  size;
- ``decode_live_page_pct``: the key pages the decode attention reads
  (the ``kv_pages_live`` stat) over the page table's entries (slots times
  pages a slot), mean over the window's decode steps; null when reducing
  a kept trace, which does not hold the table's shape;
- ``device_offset_ms``: the bounds on the device's clock minus the
  host's that causality gives (each program run starts after its
  ``dispatch`` span starts and ends before its ``host_sync`` span ends).
  Device events are moved onto the host's clock by their midpoint, and
  ``host_idle_pct_at_bounds`` gives the host idle share at either bound;
  where no pairing of steps and runs bounds it (null), they are not
  moved.

The first form adds the cell's end-to-end metrics and the benchmark's
per-layer metrics read from the same traced window. It reads the first
device only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT_DIR = ROOT / ".bench_out"
SPAN_PREFIX = "engine."
SYNC = "host_sync"
MODULES_LINE = "XLA Modules"
# engine step kind -> the jitted function its step runs
PROGRAM = {"decode": "jit_decode_step", "chunk": "jit_chunk_only",
           "chunk+decode": "jit_chunk_step", "verify": "jit_verify_step"}
CHUNK_KINDS = ("chunk", "chunk+decode")
MAX_SHIFT = 3     # steps the first program run may lie from the first step


@dataclasses.dataclass
class Recording:
    """What a trace holds for one device, times in ns on the trace's clock."""

    spans: list     # (phase, start, end, stats) engine.* host events
    runs: list      # (program, start, end) of the XLA Modules line
    ops: list       # (name, start, end) of the XLA Ops line


def load(path) -> Recording:
    """Read the first device's program runs and ops, and the host's
    ``engine.*`` events, from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    from bench import trace_reduce

    spans, planes = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            planes[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            spans.extend(
                (e.name[len(SPAN_PREFIX):], int(e.start_ns),
                 int(e.start_ns + e.duration_ns), dict(e.stats))
                for line in plane.lines for e in line.events
                if e.name.startswith(SPAN_PREFIX))
    if not planes:
        raise ValueError(f"no TPU device plane in {path}")
    runs, ops = [], []
    for line in planes[min(planes)].lines:
        evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
               for e in line.events]
        if line.name == MODULES_LINE:
            runs = [(n.split("(", 1)[0], a, b) for n, a, b in evs]
        elif line.name == trace_reduce.OPS_LINE:
            ops = evs
    return Recording(spans=sorted(spans, key=lambda e: (e[1], -e[2])),
                     runs=sorted(runs, key=lambda e: e[1]),
                     ops=sorted(ops, key=lambda e: e[1]))


def step_phases(spans: list) -> list:
    """Per ``step`` span: (step, {phase: span}) of the spans inside it."""
    out = []
    for i, sp in enumerate(spans):
        if sp[0] != "step":
            continue
        inside = {}
        for child in spans[i + 1:]:
            if child[1] >= sp[2]:
                break
            inside.setdefault(child[0], child)
        out.append((sp, inside))
    return out


def device_offset_ns(spans: list, runs: list) -> tuple[int, int] | None:
    """Bounds (lo, hi) on the device's clock minus the host's: the steps
    and the program runs pair in order, and every pair bounds it from
    both sides. The pairing is the shift of the runs against the steps,
    within ``MAX_SHIFT``, under which each step runs its kind's program
    and the bounds agree; None where no shift does. Runs of programs
    other than the step programs are left out."""
    steps = [(st, ph) for st, ph in step_phases(spans)
             if "dispatch" in ph and SYNC in ph]
    runs = [r for r in runs if r[0] in PROGRAM.values()]
    best = None
    for shift in range(-MAX_SHIFT, MAX_SHIFT + 1):
        pairs = [(steps[i], runs[i + shift]) for i in range(len(steps))
                 if 0 <= i + shift < len(runs)]
        if not pairs or any(PROGRAM.get(st[3].get("kind")) != run[0]
                            for (st, _), run in pairs):
            continue
        hi = min(run[1] - ph["dispatch"][1] for (_, ph), run in pairs)
        lo = max(run[2] - ph[SYNC][2] for (_, ph), run in pairs)
        if lo <= hi and (best is None or len(pairs) > best[0]):
            best = (len(pairs), lo, hi)
    return None if best is None else best[1:]


def innermost(spans: list) -> list:
    """Disjoint pieces (start, end, label) of the host's time: at each
    instant the innermost span that covers it, labelled
    ``<kind>/<phase>`` by the step it belongs to (module doc)."""
    pieces, stack, cur = [], [], None

    def emit(a, b, sp):
        if b > a:
            kind = next((s[3].get("kind") for s in reversed(stack)
                         if s[0] == "step"), None)
            pieces.append([a, b, sp[0], kind])

    for sp in spans:
        while stack and stack[-1][2] <= sp[1]:
            top = stack[-1]
            emit(cur, top[2], top)
            stack.pop()
            cur = top[2]
        if stack:
            emit(cur, sp[1], stack[-1])
        stack.append(sp)
        cur = sp[1]
    while stack:
        top = stack[-1]
        emit(cur, top[2], top)
        stack.pop()
        cur = top[2]
    # phases outside a step: commit belongs to the step before it,
    # admit and draft to the step after them
    kind = None
    for p in pieces:
        if p[3] is not None:
            kind = p[3]
        elif p[2] == "commit":
            p[3] = kind
    kind = None
    for p in reversed(pieces):
        if p[2] == "step" or (p[3] is not None and p[2] != "commit"):
            kind = p[3]
        elif p[3] is None:
            p[3] = kind
    return [(a, b, f"{k}/{ph}") for a, b, ph, k in pieces]


def split_idle(idle: list, pieces: list) -> dict:
    """Seconds of the idle intervals ``idle`` (sorted, disjoint) inside
    each label of ``pieces``, and outside them all (``outside``)."""
    out: dict[str, float] = {}
    j = 0
    for a, b in idle:
        covered = 0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + \
                    (hi - lo) * 1e-9
                covered += hi - lo
            k += 1
        if b - a > covered:
            out["outside"] = out.get("outside", 0.0) + (b - a - covered) * 1e-9
    return out


def host_idle_s(by_label: dict) -> float:
    """Idle seconds while the host works in a phase: every span but the
    wait for the device."""
    return sum(s for label, s in by_label.items()
               if label != "outside" and not label.endswith("/" + SYNC))


def reduce(rec: Recording, *, chunk_size: int,
           window_ns: tuple[int, int] | None = None,
           table_pages: int | None = None) -> dict:
    """The numbers of the module doc over ``window_ns`` (host clock of the
    trace; the span of the device's ops when None). ``table_pages`` is
    the page table's entries, slots times pages a slot."""
    from bench import trace_reduce

    if not rec.ops:
        raise ValueError("no device operations in the trace")
    bounds = device_offset_ns(rec.spans, rec.runs)
    pieces = innermost(rec.spans)

    def idle_at(offset):
        ops = [(a - offset, b - offset) for _, a, b in rec.ops]
        lo, hi = window_ns or (ops[0][0], max(b for _, b in ops))
        busy = trace_reduce.union(ops, lo, hi)
        return (hi - lo) * 1e-9, busy, split_idle(
            list(trace_reduce.gaps(busy, lo, hi)), pieces), (lo, hi)

    # unshifted where nothing bounds the offset
    mid = 0 if bounds is None else (bounds[0] + bounds[1]) // 2
    window_s, busy, by_label, (lo, hi) = idle_at(mid)
    busy_s = sum(b - a for a, b in busy) * 1e-9
    steps = [sp for sp in rec.spans if sp[0] == "step" and lo <= sp[1] < hi]
    n_kind: dict[str, int] = {}
    for sp in steps:
        n_kind[sp[3]["kind"]] = n_kind.get(sp[3]["kind"], 0) + 1
    per_step = {label: 1e3 * s / n_kind[label.split("/")[0]]
                for label, s in by_label.items()
                if label.split("/")[0] in n_kind}
    programs: dict[str, list[float]] = {}
    for name, a, b in rec.runs:
        if lo <= a - mid < hi:
            programs.setdefault(name, []).append((b - a) * 1e-6)
    rows = [sp[3]["chunk_tokens"] for sp in steps
            if sp[3]["kind"] in CHUNK_KINDS]
    live = [sp[3]["kv_pages_live"] for sp in steps
            if sp[3]["kind"] == "decode" and "kv_pages_live" in sp[3]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "host_idle_pct": 100.0 * host_idle_s(by_label) / window_s,
        "host_idle_pct_at_bounds": [
            100.0 * host_idle_s(idle_at(b)[2]) / window_s
            for b in bounds or ()],
        "steps": n_kind,
        "idle_ms": {k: 1e3 * v for k, v in sorted(by_label.items())},
        "idle_ms_per_step": dict(sorted(per_step.items())),
        "programs": {n: {"runs": len(d), "mean_ms": sum(d) / len(d)}
                     for n, d in sorted(programs.items())},
        "chunk_fill_pct": (100.0 * sum(rows) / (len(rows) * chunk_size)
                           if rows else None),
        "decode_live_page_pct": (
            100.0 * sum(live) / (len(live) * table_pages)
            if live and table_pages else None),
        "device_offset_ms": [b * 1e-6 for b in bounds] if bounds else None,
    }


def run_window(args) -> dict:
    """One traced window of a cell with the engine's spans on (module
    doc)."""
    from bench import harness, spec, trace_reduce
    from repro.launch.cache import enable_compile_cache
    from repro.obs import Tracer

    root = pathlib.Path(args.root).resolve()
    cs = spec.load_cell(args.workload, root)
    enable_compile_cache()
    system = harness.prepare(cs, seed=args.seed, t_start=T_START)
    system.engine.tracer = Tracer()
    kept = OUT_DIR / "step_phases.xplane.pb"
    seen = {}
    find_trace, summarize = trace_reduce.find_trace, trace_reduce.summarize

    def keep_trace(trace_dir):
        # the harness deletes its trace once read: keep a copy first
        path = find_trace(trace_dir)
        kept.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, kept)
        return path

    def see_window(tr, *, window_ns=None, step_kinds=None):
        seen["window_ns"] = window_ns
        return summarize(tr, window_ns=window_ns, step_kinds=step_kinds)

    trace_reduce.find_trace = keep_trace
    trace_reduce.summarize = see_window
    try:
        win = harness.serve_window(cs, system, seed=args.seed,
                                   seconds=args.seconds, trace=True,
                                   t_start=T_START, out_dir=OUT_DIR)
    finally:
        trace_reduce.find_trace = find_trace
        trace_reduce.summarize = summarize
    ctx = win.ctx
    ctx.peaks = spec.peaks_for(harness.device_info(cs.chips)["kind"],
                               cs.bench_dir)
    # the window on the trace's host clock, as the harness read it
    window = seen["window_ns"]
    eng = system.engine
    out = {"workload": args.workload, "seed": args.seed,
           **reduce(load(kept), chunk_size=ctx.chunk_size,
                    window_ns=window,
                    table_pages=eng.batch_size * eng.max_pages)}
    for kind, entries in (("end_to_end", cs.end_to_end),
                          ("layer_metrics", cs.per_layer)):
        out[kind] = {m["name"]: spec.load_reader(cs.bench_dir, kind,
                                                 m["name"])(ctx)
                     for m in entries}
    if args.keep:
        shutil.copy(kept, args.keep)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--keep", help="copy the window's trace here")
    ap.add_argument("--xplane", help="reduce this kept trace instead")
    ap.add_argument("--chunk-size", type=int,
                    help="the engine's chunk size, with --xplane")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    if args.xplane:
        if args.chunk_size is None:
            ap.error("--xplane needs --chunk-size")
        out = reduce(load(args.xplane), chunk_size=args.chunk_size)
    else:
        if None in (args.workload, args.seed, args.seconds):
            ap.error("--workload, --seed and --seconds, or --xplane")
        out = run_window(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
