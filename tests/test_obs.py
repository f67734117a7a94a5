"""Observability stack (DESIGN.md §8): tracer, metrics, sim timelines,
sim-vs-measured compare, and the engines' instrumentation.

Four layers are pinned here:

* the tracer itself: a disabled tracer is a strict no-op (shared span
  singleton, zero events), spans nest and export time-sorted, the ring
  buffer flags truncation, and the exporter's output passes its own
  structural validator (which in turn catches seeded corruption);
* the metrics registry: exact nearest-rank percentiles, JSON and
  Prometheus serializations, monotone counters;
* the simulator: ``simulate`` no longer mutates its input tasks,
  ``busy_by_tag`` breaks busy cycles down by tag family, and the
  resolved timeline renders to a schema-valid Chrome trace;
* the engines: one lifecycle span per request in BOTH engines' traces,
  phase sub-spans driven by the state machine (incl. a PREEMPTED span
  under the PR-6 fault injector), per-step spans annotated with the
  compile-shape kind, and the back-compat metric properties
  (``occupancy_log`` & co) reading through the registry.
"""

import json

import jax
import numpy as np
import pytest

from repro.obs import (
    DEFAULT_KIND_TO_PHASE,
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    compare_report,
    measured_phase_stats,
    tag_key,
    tasks_to_chrome,
    validate_chrome_trace,
)
from repro.serving import (
    ContinuousBatchingEngine,
    NO_FAULTS,
    Request,
    ScriptedFaults,
    ServingEngine,
)
from repro.sim import EDGE_HW, simulate
from repro.sim.engine import Task
from repro.sim.workload import serving_phase_workloads

jax.config.update("jax_enable_x64", False)


class FakeClock:
    """Deterministic clock for span-timing tests (seconds)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", track="x", args={"k": 1})
    assert s1 is s2  # shared singleton: no per-call allocation
    with s1:
        pass
    tr.begin("a")
    tr.end("a")
    tr.instant("i")
    tr.counter("c", 1.0)
    tr.complete("x", 0.0, 1.0)
    out = tr.export()
    assert out["traceEvents"] == []
    assert out["otherData"]["complete"] is True
    assert NULL_TRACER.enabled is False


def test_span_nesting_and_ordering():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", track="t"):
        clk.t = 1e-3
        with tr.span("inner", track="t"):
            clk.t = 2e-3
        clk.t = 5e-3
    evs = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    by_name = {e["name"]: e for e in evs}
    # inner closed first, so it exports before outer but STARTS later
    assert [e["name"] for e in evs] == ["outer", "inner"]
    assert by_name["inner"]["ts"] == pytest.approx(1e3)   # us
    assert by_name["inner"]["dur"] == pytest.approx(1e3)
    assert by_name["outer"]["ts"] == pytest.approx(0.0)
    assert by_name["outer"]["dur"] == pytest.approx(5e3)
    # containment == nesting in the Chrome model
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert o["tid"] == i["tid"]
    assert validate_chrome_trace(tr.export()) == []


def test_disabled_tracer_opens_no_annotation(monkeypatch):
    from repro.obs import trace as trace_mod

    def refuse():
        raise AssertionError("a disabled tracer reached the profiler")

    monkeypatch.setattr(trace_mod, "_annotation", refuse)
    tr = Tracer(enabled=False)
    span = tr.span("step", track="engine", args={"k": 1})
    assert span is trace_mod._NULL_SPAN
    with span:
        pass
    assert tr.export()["traceEvents"] == []


def test_profiler_sink_annotates_device_trace(tmp_path):
    """Under a running profiler trace, an enabled tracer's spans land in
    it as ``<track>.<name>`` host events, nested as they ran, with the
    args the span held when it closed as event stats; the in-memory trace
    is unchanged by the sink."""
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        args = {"kind": "decode"}
        with tr.span("step", track="engine", args=args):
            with tr.span("pack", track="engine"):
                jnp.ones(8).block_until_ready()
            with tr.span("commit", track="engine"):
                pass
            args["step"] = 3  # filled in before the span closes
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    evs = {e.name: e for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("engine.")}
    assert set(evs) == {"engine.step", "engine.pack", "engine.commit"}
    step, pack, commit = (evs[f"engine.{n}"] for n in
                          ("step", "pack", "commit"))
    assert dict(step.stats) == {"kind": "decode", "step": 3}
    assert dict(pack.stats) == {}

    def end(e):
        return e.start_ns + e.duration_ns

    assert step.start_ns <= pack.start_ns < end(pack) <= commit.start_ns
    assert end(commit) <= end(step)
    out = tr.export()
    assert validate_chrome_trace(out) == []
    assert [(e["name"], e.get("args")) for e in out["traceEvents"]
            if e["ph"] == "X"] == [("step", {"kind": "decode", "step": 3}),
                                   ("pack", None), ("commit", None)]


def test_ring_buffer_truncation_is_flagged():
    tr = Tracer(max_events=4)
    for k in range(10):
        tr.instant(f"e{k}")
    out = tr.export()
    assert out["otherData"]["dropped_events"] == 6
    assert out["otherData"]["complete"] is False
    names = [e["name"] for e in out["traceEvents"]]
    assert "ring_buffer_truncated" in names
    # the newest events survive, the oldest are the ones dropped
    assert "e9" in names and "e0" not in names
    assert validate_chrome_trace(out) == []


def test_validator_catches_corruption():
    ok = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0.0, "pid": 0, "tid": 0},
        {"name": "a", "ph": "E", "ts": 1.0, "pid": 0, "tid": 0},
    ]}
    assert validate_chrome_trace(ok) == []
    unmatched = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0.0, "pid": 0, "tid": 0},
    ]}
    assert any("unclosed" in e for e in validate_chrome_trace(unmatched))
    misnested = {"traceEvents": [
        {"name": "a", "ph": "B", "ts": 0.0, "pid": 0, "tid": 0},
        {"name": "b", "ph": "E", "ts": 1.0, "pid": 0, "tid": 0},
    ]}
    assert any("mis-nested" in e for e in validate_chrome_trace(misnested))
    unsorted_ts = {"traceEvents": [
        {"name": "a", "ph": "i", "ts": 5.0, "s": "t", "pid": 0, "tid": 0},
        {"name": "b", "ph": "i", "ts": 1.0, "s": "t", "pid": 0, "tid": 0},
    ]}
    assert any("time-sorted" in e for e in validate_chrome_trace(unsorted_ts))
    bad_dur = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": -1.0, "pid": 0,
         "tid": 0}]}
    assert any("dur" in e for e in validate_chrome_trace(bad_dur))
    lying = {"traceEvents": [],
             "otherData": {"dropped_events": 3, "complete": True}}
    assert any("complete" in e for e in validate_chrome_trace(lying))
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def test_trace_json_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("s", args={"k": 1}):
        tr.instant("mark")
    path = tmp_path / "t.json"
    tr.write(path)
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    assert {e["name"] for e in loaded["traceEvents"]} >= {"s", "mark"}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles_exact():
    m = MetricsRegistry()
    h = m.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert m.histogram("empty").summary()["p95"] == 0.0


def test_counter_gauge_series():
    m = MetricsRegistry()
    c = m.counter("n")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = m.gauge("occ")
    g.record(3)
    g.record(5)
    assert g.value == 5 and g.series == [3, 5]
    s = m.series("walltimes")
    s.observe(0, 1.0)
    s.observe(0, 2.0)
    s.observe(1, 3.0)
    assert s.by_key == {0: [1.0, 2.0], 1: [3.0]}
    # get-or-create returns the same object
    assert m.counter("n") is c


def test_metrics_serialization(tmp_path):
    m = MetricsRegistry()
    m.counter("serving.preemptions", help="evictions").inc(2)
    m.gauge("pool.pages_used").record(7)
    h = m.histogram("engine.step_s.decode")
    h.observe(0.5)
    h.observe(1.5)
    m.series("token_walltime_s").observe(3, 0.25)

    j = m.to_json()
    assert j["counters"]["serving.preemptions"] == 2
    assert j["gauges"]["pool.pages_used"] == {"value": 7, "series": [7]}
    assert j["histograms"]["engine.step_s.decode"]["count"] == 2
    assert j["series"]["token_walltime_s"] == {"3": [0.25]}
    p = tmp_path / "m.json"
    m.write_json(p)
    assert json.loads(p.read_text()) == j

    prom = m.to_prometheus()
    assert "# TYPE serving_preemptions counter" in prom
    assert "serving_preemptions 2" in prom
    assert "# HELP serving_preemptions evictions" in prom
    assert "pool_pages_used 7" in prom
    assert 'engine_step_s_decode{quantile="0.5"}' in prom
    assert "engine_step_s_decode_count 2" in prom
    assert "token_walltime" not in prom  # keyed series are JSON-only


# ---------------------------------------------------------------------------
# simulator: non-mutation, busy_by_tag, timeline -> Chrome trace
# ---------------------------------------------------------------------------


def _toy_tasks():
    return [
        Task(unit="DMA", cycles=10, tag="K0", dram_read_bytes=256),
        Task(unit="MAC", cycles=20, deps=(0,), tag="C0.0", mac_ops=64),
        Task(unit="VEC", cycles=5, deps=(1,), tag="P0.0", vec_ops=16),
        Task(unit="DMA", cycles=10, deps=(2,), tag="O0",
             dram_write_bytes=128),
    ]


def test_simulate_does_not_mutate_input():
    tasks = _toy_tasks()
    r = simulate(tasks, EDGE_HW, return_timeline=True)
    assert all(t.start == 0.0 and t.end == 0.0 for t in tasks)
    assert r.timeline is not None and len(r.timeline) == len(tasks)
    assert r.timeline[-1].end == r.cycles == 45.0
    assert [t.start for t in r.timeline] == [0.0, 10.0, 30.0, 35.0]
    # same list simulates identically a second time (no hidden state)
    assert simulate(tasks, EDGE_HW).cycles == r.cycles
    # without the flag no timeline is built
    assert simulate(tasks, EDGE_HW).timeline is None


def test_busy_by_tag_groups_tag_families():
    r = simulate(_toy_tasks(), EDGE_HW)
    assert r.busy_by_tag == {"C": 20.0, "K": 10.0, "O": 10.0, "P": 5.0}
    assert sum(r.busy_by_tag.values()) == sum(r.busy.values())
    # DRAM bytes are device-scaled like the top-level counters
    assert r.dram_bytes_by_tag == {"K": 256 * EDGE_HW.cores,
                                   "O": 128 * EDGE_HW.cores}
    assert tag_key("C3.1") == "C"
    assert tag_key("Vreload0.2") == "Vreload"
    assert tag_key("K+V12") == "K+V"


def test_timeline_renders_to_valid_chrome_trace():
    r = simulate(_toy_tasks(), EDGE_HW, return_timeline=True)
    trace = tasks_to_chrome(r.timeline, EDGE_HW.freq_ghz, name="toy")
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"]["time_unit"] == "us"
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M"}
    assert tracks == {"MXU", "VEC", "DMA"}  # sim "MAC" renders as MXU
    xs = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    scale = 1.0 / (EDGE_HW.freq_ghz * 1e3)
    assert xs["C"]["ts"] == pytest.approx(10.0 * scale)
    assert xs["C"]["dur"] == pytest.approx(20.0 * scale)
    assert xs["K"]["args"]["dram_read_bytes"] == 256
    # cycles mode: raw cycle timestamps
    raw = tasks_to_chrome(r.timeline)
    assert raw["otherData"]["time_unit"] == "cycles"
    assert {e["name"]: e for e in raw["traceEvents"]
            if e["ph"] == "X"}["C"]["ts"] == 10.0


# ---------------------------------------------------------------------------
# sim-vs-measured compare
# ---------------------------------------------------------------------------


def _step_trace(kind_durs):
    """A minimal measured trace: one 'step' X event per (kind, dur_us)."""
    tr = Tracer(clock=iter(range(10 ** 6)).__next__)
    ts = 0.0
    for kind, dur in kind_durs:
        tr.complete("step", ts, dur, track="engine", args={"kind": kind})
        ts += dur
    return tr.export()


def test_compare_report_toy_scenario():
    # measured: decode steps 100us, chunk steps 300us; sim priced so
    # decode comes out exactly 1x (375k cycles @ 3.75 GHz == 100 us)
    trace = _step_trace([("decode", 100.0), ("decode", 100.0),
                         ("chunk", 300.0), ("chunk+decode", 300.0),
                         ("unknown_kind", 7.0)])
    stats = measured_phase_stats(trace)
    assert stats["decode"]["count"] == 2
    assert stats["prefill_chunk"]["count"] == 2  # both chunk kinds
    assert "unknown_kind" not in str(stats)

    report = compare_report(trace, {"decode": 375_000.0,
                                    "prefill_chunk": 750_000.0},
                            freq_ghz=3.75, meta={"scenario": "toy"})
    d = report["phases"]["decode"]
    assert d["sim_us"] == pytest.approx(100.0)
    assert d["measured_over_sim_p50"] == pytest.approx(1.0)
    p = report["phases"]["prefill_chunk"]
    assert p["measured_over_sim_p50"] == pytest.approx(1.5)
    assert report["matched_phases"] == ["decode", "prefill_chunk"]
    assert report["unmatched_phases"] == []
    assert report["meta"] == {"scenario": "toy"}


def test_compare_report_flags_unmatched_phases():
    trace = _step_trace([("decode", 50.0)])
    report = compare_report(trace, {"prefill_chunk": 1000.0}, freq_ghz=3.75)
    assert report["matched_phases"] == []
    assert report["unmatched_phases"] == ["decode", "prefill_chunk"]
    assert report["phases"]["decode"]["measured_over_sim_p50"] is None


def test_serving_phase_workloads_shapes():
    w = serving_phase_workloads("x", [48, 8, 24, 16, 5], 16,
                                heads=2, emb=16, group=2, batch=4)
    # "verify" only appears when spec= is set (DESIGN.md §9), so a plain
    # build covers every compare phase except it
    assert set(w) == set(DEFAULT_KIND_TO_PHASE.values()) - {"verify"}
    assert set(serving_phase_workloads(
        "x", [48, 8, 24, 16, 5], 16, heads=2, emb=16, group=2, batch=4,
        spec=4)) == set(DEFAULT_KIND_TO_PHASE.values())
    assert w["decode"].kv_lens == (56, 32, 24, 16)  # top-4, +max_new/2
    assert w["prefill_chunk"].prompt == 48          # longest prompt
    assert w["prefill_chunk"].decode_kv_lens == (32, 24, 16)
    assert w["prefill_chunk"].n_chunks(16) == 3
    assert w["prefill_chunk"].n_chunks(None) == 1
    with pytest.raises(ValueError):
        serving_phase_workloads("x", [], 4, heads=1, emb=8)


# ---------------------------------------------------------------------------
# engine instrumentation (shared smoke model, like test_lifecycle.py)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke():
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def cont_engine(smoke):
    cfg, model, params = smoke
    return ContinuousBatchingEngine(model, params, max_len=40, batch_size=2,
                                    page_size=4, chunk_size=8)


@pytest.fixture(scope="module")
def wave_engine(smoke):
    cfg, model, params = smoke
    return ServingEngine(model, params, max_len=40, batch_size=2)


def _requests(cfg, spec):
    return [Request(rid=i,
                    prompt=np.random.default_rng(7 + i).integers(
                        3, cfg.vocab_size, size=(n,)).astype(np.int32),
                    max_new_tokens=m, eos_id=-2)
            for i, (n, m) in enumerate(spec)]


def _traced_serve(engine, cfg, spec, injector=NO_FAULTS):
    tr = Tracer()
    engine.tracer = tr
    engine.injector = injector
    try:
        out = engine.serve(_requests(cfg, spec))
    finally:
        engine.tracer = NULL_TRACER
        engine.injector = NO_FAULTS
    return out, tr.export()


SPEC = [(5, 4), (9, 3), (13, 2)]


def _request_spans(trace):
    begins = [e for e in trace["traceEvents"]
              if e["ph"] == "B" and e["name"] == "request"]
    ends = [e for e in trace["traceEvents"]
            if e["ph"] == "E" and e["name"] == "request"]
    return begins, ends


def test_cont_engine_trace_lifecycle_and_steps(smoke, cont_engine):
    cfg, _, _ = smoke
    out, trace = _traced_serve(cont_engine, cfg, SPEC)
    assert validate_chrome_trace(trace) == []
    begins, ends = _request_spans(trace)
    assert len(begins) == len(SPEC) and len(ends) == len(SPEC)
    # terminal args ride the closing E event
    for e in ends:
        assert e["args"]["state"] == "finished"
        assert e["args"]["preemptions"] == 0
    # every request's phase spans nest inside its lifecycle span
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"prefilling", "decoding", "step", "dispatch",
            "host_sync"} <= names
    kinds = {(e.get("args") or {}).get("kind")
             for e in trace["traceEvents"]
             if e["ph"] == "X" and e["name"] == "step"}
    kinds.discard(None)
    assert kinds <= {"decode", "chunk", "chunk+decode"}
    assert "decode" in kinds
    # pool occupancy rides as a counter track
    assert any(e["ph"] == "C" and e["name"] == "pool.pages_used"
               for e in trace["traceEvents"])
    # back-compat metric views read through the registry
    assert cont_engine.occupancy_log
    assert set(cont_engine.token_walltimes) == {0, 1, 2}
    assert cont_engine.preemption_count == 0


def _kind(entry) -> str:
    if not entry["prefill_in_flight"]:
        return "decode"
    return "chunk+decode" if entry["live_decode"] else "chunk"


def test_cont_engine_step_phase_spans(smoke, cont_engine):
    """Each engine step is an ``admit`` span, a ``step`` span holding
    pack, dispatch and host_sync in that order, then a ``commit`` span;
    the step's args are its ``step_log`` entry; the chunk rows add up to
    the prompts; and each step-time histogram value still runs from the
    step's start to the end of its host sync."""
    cfg, _, _ = smoke
    spec = [(5, 4), (19, 3), (13, 2)]  # one prompt takes three chunks
    out, trace = _traced_serve(cont_engine, cfg, spec)
    phases = ("admit", "step", "draft", "pack", "dispatch", "host_sync",
              "commit")
    evs = sorted((e for e in trace["traceEvents"]
                  if e["ph"] == "X" and e["name"] in phases),
                 key=lambda e: (e["ts"], -e["dur"]))
    assert len({e["tid"] for e in evs}) == 1  # all on the engine track
    steps = [e for e in evs if e["name"] == "step"]
    log = cont_engine.step_log
    assert len(steps) == len(log)
    hist = {k: list(cont_engine.metrics.histogram(
        f"engine.step_s.{k}").values) for k in ("decode", "chunk",
                                                 "chunk+decode")}
    assert sum(map(len, hist.values())) == len(steps)
    used = dict.fromkeys(hist, 0)
    for i, (st, entry) in enumerate(zip(steps, log)):
        end = st["ts"] + st["dur"]
        inside = [e for e in evs if st["ts"] <= e["ts"] and
                  e["ts"] + e["dur"] <= end and e is not st]
        assert [e["name"] for e in inside] == [
            "pack", "dispatch", "host_sync"]
        # the step's neighbours on the track: its admit, then its commit
        j = evs.index(st)
        admit, commit = evs[j - 1], evs[j + len(inside) + 1]
        assert (admit["name"], commit["name"]) == ("admit", "commit")
        assert admit["ts"] + admit["dur"] <= st["ts"]
        assert end <= commit["ts"]
        assert st["args"] == {"step": st["args"]["step"],
                              "kind": _kind(entry),
                              "live_decode": entry["live_decode"],
                              "chunk_tokens": entry["chunk_tokens"],
                              "pages_used": cont_engine.occupancy_log[i],
                              "kv_pages_live": st["args"]["kv_pages_live"]}
        # the decode slots' key pages: at least one a slot, all held
        assert (entry["live_decode"] <= st["args"]["kv_pages_live"]
                <= cont_engine.occupancy_log[i])
        # the histogram's interval opens between admit and step and
        # closes between host_sync and commit (the clocks are one)
        kind = st["args"]["kind"]
        value = hist[kind][used[kind]]
        used[kind] += 1
        sync = inside[2]
        eps = 1e-9
        assert (sync["ts"] + sync["dur"] - st["ts"]) * 1e-6 - eps <= value
        assert value <= (commit["ts"] - admit["ts"] - admit["dur"]) * 1e-6 \
            + eps
    rows = sum(n for n, _ in spec)
    assert sum(e["chunk_tokens"] for e in log) == rows
    assert max(e["chunk_tokens"] for e in log) == cont_engine.chunk_size
    assert all(len(out[i]) == m for i, (_, m) in enumerate(spec))


def test_cont_engine_trace_preemption_nesting(smoke, cont_engine):
    cfg, _, _ = smoke
    # PR-6 fault injector: force one pool exhaustion mid-decode -> the
    # victim's lifecycle span must contain a PREEMPTED phase span and
    # its terminal args must count the preemption
    out, trace = _traced_serve(
        cont_engine, cfg, SPEC,
        injector=ScriptedFaults(exhaust_at_appends={2}))
    assert validate_chrome_trace(trace) == []
    begins, ends = _request_spans(trace)
    assert len(begins) == len(SPEC) and len(ends) == len(SPEC)
    preempted = [e for e in trace["traceEvents"]
                 if e["ph"] == "B" and e["name"] == "preempted"]
    assert preempted, "no PREEMPTED phase span under forced exhaustion"
    assert any(e["args"]["preemptions"] > 0 for e in ends)
    assert any(e["ph"] == "i" and e["name"] == "preempt"
               for e in trace["traceEvents"])
    assert cont_engine.preemption_count >= 1
    assert cont_engine.recompute_tokens > 0
    # registry mirrors the trace
    m = cont_engine.metrics.to_json()
    assert m["counters"]["serving.preemptions"] >= 1
    assert m["histograms"]["engine.host_sync_s"]["count"] > 0


def test_wave_engine_trace_lifecycle(smoke, wave_engine):
    cfg, _, _ = smoke
    out, trace = _traced_serve(wave_engine, cfg, SPEC)
    assert validate_chrome_trace(trace) == []
    begins, ends = _request_spans(trace)
    assert len(begins) == len(SPEC) and len(ends) == len(SPEC)
    kinds = {(e.get("args") or {}).get("kind")
             for e in trace["traceEvents"]
             if e["ph"] == "X" and e["name"] == "step"}
    assert kinds == {"wave_decode"}
    assert {"prefill_dispatch", "host_sync"} <= {
        e["name"] for e in trace["traceEvents"]}
    assert set(wave_engine.token_walltimes) == {0, 1, 2}


def test_engines_untraced_by_default(smoke, cont_engine):
    cfg, _, _ = smoke
    assert cont_engine.tracer is NULL_TRACER
    out = cont_engine.serve(_requests(cfg, SPEC))
    assert len(out) == len(SPEC)
    assert NULL_TRACER.export()["traceEvents"] == []
    # metrics stay on even without tracing (they ARE the bench numbers)
    assert cont_engine.occupancy_log
    assert cont_engine.metrics.histogram("engine.step_s.decode").count > 0
