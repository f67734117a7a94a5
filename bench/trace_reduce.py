"""From a profiler trace to the numbers the benchmark reports.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

- device operations: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane (name, start, duration in ns);
- busy time: the union of those intervals inside the window, averaged
  over the devices; idle share is 1 - busy / window;
- kernel time: the summed durations of the operations of one family (an
  event is named by its HLO text, ``%paged_decode_attention.9 = ...``, and
  a Pallas call's instruction after the function that makes it); a
  scan's ``while`` holds the ops of its body, so the breakdown leaves it
  out and busy time counts it;
- idle time, summed by what the host was doing in each gap: the
  harness wraps every engine step in a ``bench_step <k>`` annotation and
  every sleep for an arrival in ``bench_arrival_wait``, on the profiler's
  own clock, so a gap inside a step's annotation is host work in that
  step, and a gap in none of them lies between steps.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
STEP_ANN = re.compile(r"^bench_step (\d+)$")
WAIT_ANN = "bench_arrival_wait"
TOP = 10
# ops that contain others on the same line (a scan's loop): busy time
# counts them, the per-op breakdown counts what runs inside them
CONTAINERS = frozenset({"while", "conditional", "call"})


def find_trace(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


@dataclasses.dataclass
class Trace:
    ops: dict            # device id -> [(name, start_ns, end_ns)] sorted
    host: list           # [(name, start_ns, end_ns)] host annotations


def load(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
            ops[int(m.group(1))] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if STEP_ANN.match(e.name) or e.name == WAIT_ANN:
                        host.append((e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)))
    return Trace(ops=ops, host=sorted(host, key=lambda e: e[1]))


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged intervals of ``intervals`` ((name, start, end) or
    (start, end)) clipped to [lo, hi]."""
    spans = sorted((max(iv[-2], lo), min(iv[-1], hi)) for iv in intervals
                   if iv[-1] > lo and iv[-2] < hi)
    out: list[list[int]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int):
    prev = lo
    for a, b in busy:
        if a > prev:
            yield prev, a
        prev = max(prev, b)
    if hi > prev:
        yield prev, hi


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over devices
    op_s: dict                    # op family -> seconds, mean over devices
    idle_gaps: list               # [(label, total seconds)] largest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, family: str) -> float:
        """Device seconds of the ops of one family (``op_family``)."""
        return self.op_s.get(family, 0.0)

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:TOP]]}


def op_family(name: str) -> str:
    """One row per kind of op, not per HLO instruction: an event named
    ``%fusion.123 = bf16[...] fusion(...)`` (the trace names an op by its
    HLO text) is ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def summarize(tr: Trace, *, window_ns: tuple[int, int] | None = None,
              step_kinds: dict | None = None) -> Summary:
    """Reduce a loaded trace over ``window_ns`` (trace clock; the span of
    the device operations when None). ``step_kinds`` maps an engine step
    index to its kind, for naming gaps."""
    if not tr.ops or not any(tr.ops.values()):
        raise ValueError("no device operations in the trace")
    if window_ns is None:
        starts = [e[1] for evs in tr.ops.values() for e in evs]
        ends = [e[2] for evs in tr.ops.values() for e in evs]
        window_ns = (min(starts), max(ends))
    lo, hi = window_ns
    busy_total, op_s = 0.0, {}
    busy0 = None
    for dev, evs in sorted(tr.ops.items()):
        merged = union(evs, lo, hi)
        busy_total += sum(b - a for a, b in merged) * 1e-9
        if busy0 is None:
            busy0 = merged
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            key = op_family(name)
            if b > a and key not in CONTAINERS:
                op_s[key] = op_s.get(key, 0.0) + (b - a) * 1e-9
    n_dev = len(tr.ops)
    by_label: dict[str, list[float]] = {}
    for a, b in gaps(busy0, lo, hi):
        label = _label(tr.host, (a + b) // 2, step_kinds or {})
        by_label.setdefault(label, []).append((b - a) * 1e-9)
    idle = sorted(((f"{label} ({len(g)} gaps, longest {max(g) * 1e3:.3f} ms)",
                    sum(g)) for label, g in by_label.items()),
                  key=lambda x: -x[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_dev,
                   op_s={k: v / n_dev for k, v in op_s.items()},
                   idle_gaps=idle)


def _label(host, t: int, step_kinds: dict) -> str:
    for name, a, b in host:
        if a <= t < b:
            if name == WAIT_ANN:
                return "host waits for the next arrival"
            k = int(STEP_ANN.match(name).group(1))
            return f"host inside an engine step ({step_kinds.get(k, '?')})"
        if a > t:
            break
    return "host between engine steps"


def host_offset_ns(tr: Trace, perf_by_step: dict) -> int | None:
    """Trace clock minus ``perf_counter`` in ns, from the step annotations
    whose host-clock starts are known (``perf_by_step``: step index ->
    perf_counter seconds at the annotation's start)."""
    diffs = sorted(a - int(perf_by_step[int(STEP_ANN.match(n).group(1))]
                           * 1e9)
                   for n, a, _ in tr.host
                   if STEP_ANN.match(n)
                   and int(STEP_ANN.match(n).group(1)) in perf_by_step)
    return diffs[len(diffs) // 2] if diffs else None
