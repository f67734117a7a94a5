"""The reduction of ``scripts/step_phases.py``: device idle time split by
the engine's step phases, on hand-made intervals and on a device trace
recorded on a TPU v5e chip (the smoke cell's window, served with the
engine's tracer on, kept by the script's ``--keep``)."""

import importlib.util
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE = ROOT / "tests" / "data" / "step_phases.xplane.pb"
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def sp():
    # the script reads traces with ``bench.trace_reduce``
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location(
        "step_phases_script", ROOT / "scripts" / "step_phases.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _cycle(t, kind, chunk_tokens=0):
    """One engine step's spans from ``t`` ms: admit 1, step 6 holding
    pack 1, dispatch 1, host_sync 3, then commit 1; 9 ms in all."""
    stats = {"kind": kind, "chunk_tokens": chunk_tokens}
    return [("admit", t, t + 1, {}), ("step", t + 1, t + 7, stats),
            ("pack", t + 1.5, t + 2.5, {}), ("dispatch", t + 2.5, t + 3.5, {}),
            ("host_sync", t + 3.5, t + 6.5, {}), ("commit", t + 7, t + 8, {})]


def _recording(sp, kinds, *, offset_ms=-0.25, extra_run=False):
    spans, runs, ops = [], [], []
    for i, (kind, rows) in enumerate(kinds):
        t = 9 * i
        spans += _cycle(t, kind, rows)
        # the device runs the step from 0.4 ms after the dispatch starts
        # to 0.4 ms before the sync ends, on a clock ``offset_ms`` off
        a, b = t + 2.9 + offset_ms, t + 6.1 + offset_ms
        runs.append((sp.PROGRAM[kind], a, b))
        ops += [("fusion", a, a + 1.0), ("fusion", a + 1.5, b)]
    if extra_run:  # a run the trace caught before the first step span
        runs.insert(0, ("jit_decode_step", -8.0, -7.0))
        ops.insert(0, ("fusion", -8.0, -7.0))

    def ns(evs):
        return [(e[0], int(e[1] * MS), int(e[2] * MS), *e[3:]) for e in evs]

    return sp.Recording(spans=sorted(ns(spans), key=lambda e: (e[1], -e[2])),
                        runs=ns(runs), ops=ns(ops))


STEPS = [("chunk", 20), ("chunk+decode", 12), ("decode", 0), ("decode", 0)]


def test_innermost_labels_each_phase_by_its_step(sp):
    rec = _recording(sp, STEPS)
    pieces = sp.innermost(rec.spans)
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    labels = [p[2] for p in pieces]
    assert labels[:7] == ["chunk/admit", "chunk/step", "chunk/pack",
                          "chunk/dispatch", "chunk/host_sync", "chunk/step",
                          "chunk/commit"]
    # admit goes with the step after it, commit with the one before it
    assert labels[7] == "chunk+decode/admit"
    assert labels[-1] == "decode/commit"


@pytest.mark.parametrize("offset_ms,extra_run", [(-0.25, False),
                                                  (0.3, False),
                                                  (-0.25, True)])
def test_device_offset_bounds_hold_the_true_offset(sp, offset_ms, extra_run):
    rec = _recording(sp, STEPS, offset_ms=offset_ms, extra_run=extra_run)
    lo, hi = sp.device_offset_ns(rec.spans, rec.runs)
    assert lo <= offset_ms * MS <= hi
    assert lo == pytest.approx((offset_ms - 0.4) * MS, abs=2)
    assert hi == pytest.approx((offset_ms + 0.4) * MS, abs=2)


def test_device_offset_needs_runs_that_pair_with_steps(sp):
    rec = _recording(sp, STEPS)
    wrong = [("jit_verify_step", a, b) for _, a, b in rec.runs]
    assert sp.device_offset_ns(rec.spans, wrong) is None
    # other programs' runs between the steps' are left out
    extra = rec.runs + [("jit_copy", a + MS // 2, a + MS)
                        for _, a, _ in rec.runs]
    assert sp.device_offset_ns(rec.spans, sorted(
        extra, key=lambda r: r[1])) == sp.device_offset_ns(rec.spans,
                                                           rec.runs)
    # unpaired, the device's events stay where the trace put them
    rec.runs = wrong
    out = sp.reduce(rec, chunk_size=32, window_ns=(0, 36 * MS))
    assert out["device_offset_ms"] is None
    assert out["host_idle_pct_at_bounds"] == []


def test_reduce_splits_all_idle_and_host_idle_is_part_of_it(sp):
    rec = _recording(sp, STEPS)
    out = sp.reduce(rec, chunk_size=32, window_ns=(0, 36 * MS))
    idle_ms = out["window_s"] * (out["idle_pct"] / 100) * 1e3
    assert sum(out["idle_ms"].values()) == pytest.approx(idle_ms)
    per = out["idle_ms_per_step"]
    assert 0 < out["host_idle_pct"] <= out["idle_pct"]
    for pct in out["host_idle_pct_at_bounds"]:
        assert 0 < pct <= out["idle_pct"]
    # device busy 2.7 of each 9 ms cycle: 1 ms from 0.4 ms into the
    # dispatch, then 1.6 ms to 0.4 ms before the sync ends
    assert {k: round(v, 6) for k, v in per.items()
            if k.startswith("decode/")} == {
        "decode/admit": 1.0, "decode/step": 1.0, "decode/pack": 1.0,
        "decode/dispatch": 0.4, "decode/host_sync": 0.9,
        "decode/commit": 1.0}
    assert out["idle_ms"]["outside"] == pytest.approx(4 * 1.0)
    assert out["host_idle_pct"] == pytest.approx(100 * 4 * 4.4 / 36)
    assert out["steps"] == {"chunk": 1, "chunk+decode": 1, "decode": 2}
    assert out["programs"]["jit_decode_step"] == {
        "runs": 2, "mean_ms": pytest.approx(3.2)}
    assert out["idle_pct"] == pytest.approx(100 * (1 - 4 * 2.7 / 36))
    assert out["chunk_fill_pct"] == pytest.approx(100 * 32 / 64)


def test_reduce_reads_decode_live_pages(sp):
    rec = _recording(sp, STEPS)
    steps = [e[3] for e in rec.spans if e[0] == "step"]
    # hand-made kv_pages_live: both decode steps, and a chunk+decode step
    # that the mean leaves out
    for stats, pages in zip(steps[1:], (999, 30, 50)):
        stats["kv_pages_live"] = pages
    out = sp.reduce(rec, chunk_size=32, window_ns=(0, 36 * MS),
                    table_pages=16 * 80)
    assert out["decode_live_page_pct"] == pytest.approx(
        100 * (30 + 50) / 2 / (16 * 80))
    # without the table's shape there is no reading
    assert sp.reduce(rec, chunk_size=32, window_ns=(0, 36 * MS))[
        "decode_live_page_pct"] is None


@pytest.fixture(scope="module")
def recording(sp):
    return sp.load(TRACE)


def test_recorded_steps_hold_their_phases_in_order(sp, recording):
    spans = recording.spans
    steps = sp.step_phases(spans)
    assert len(steps) >= 10
    kinds = set()
    for (_, a, b, stats), inside in steps:
        assert list(inside) == ["pack", "dispatch", "host_sync"]
        assert set(stats) >= {"step", "kind", "live_decode",
                              "chunk_tokens", "pages_used"}
        assert (stats["chunk_tokens"] > 0) == (stats["kind"] != "decode")
        kinds.add(stats["kind"])
        # the commit follows its step, and nothing else comes between
        j = next(i for i, e in enumerate(spans) if e[1] >= b)
        assert spans[j][0] == "commit"
    assert {"decode", "chunk"} <= kinds


def test_recorded_window_reduces_under_one_clock_offset(sp, recording):
    lo, hi = sp.device_offset_ns(recording.spans, recording.runs)
    assert lo <= hi
    assert -3 * MS < lo and hi < 3 * MS
    out = sp.reduce(recording, chunk_size=32)
    assert 0 < out["host_idle_pct"] <= out["idle_pct"]
    idle_ms = out["window_s"] * (out["idle_pct"] / 100) * 1e3
    assert sum(out["idle_ms"].values()) == pytest.approx(idle_ms)
    # the device idles in every phase of a step, the sync included
    assert {label.split("/")[1] for label in out["idle_ms_per_step"]} == {
        "admit", "step", "pack", "dispatch", "host_sync", "commit"}
    runs = {n: p["runs"] for n, p in out["programs"].items()}
    assert runs == {sp.PROGRAM[k]: n for k, n in out["steps"].items()}
    assert 0 < out["chunk_fill_pct"] <= 100
