"""One run of one cell: set up, serve the window, read metrics, check.

``run_cell`` is the whole run after the look for a chip: ``bench/run.py``
makes that look, and the tests call ``run_cell`` on the CPU at smoke
sizes. Set-up is everything from the process start to the window's
opening: JAX start, weights from the seed, the warm-up ``serve`` that
loads every step program from the compile cache, and, for a backlog, the
fill of every slot. The window then runs for ``seconds``; with
``trace`` the device profiler records it and per-layer metrics are
reported instead of end-to-end ones. After the window the program's
state is freed and the served tokens are checked against the reference.
"""

from __future__ import annotations

import dataclasses
import gc
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import spec as spec_mod
from bench.traffic import generate
from bench.traffic.driver import OpenLoopDriver

CHECK_REQUESTS = 4      # requests the check reads, at most
WARMUP_MAX_NEW = 3


@dataclasses.dataclass
class Context:
    """What the metric readers read (``bench/end_to_end``,
    ``bench/layer_metrics``)."""

    cell: dict
    config: dict
    arch: object             # the module bench/archs/<arch>
    end_to_end: tuple        # names of the cell's end-to-end metrics
    slots: int
    pool_pages: int          # usable pages (the scratch page excluded)
    page_size: int
    chunk_size: int
    w0: float                # window, host clock
    w1: float
    setup_s: float
    requests: list           # dicts: rid, due, admit, stamps, prompt_len, ...
    steps: list              # dicts: t, kind, live, pages_used, step_s
    trace: object = None     # bench.trace_reduce.Summary, with --trace 1
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    def in_window(self, t: float) -> bool:
        return self.w0 <= t <= self.w1

    def window_steps(self) -> list:
        return [s for s in self.steps if self.in_window(s["t"])]


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _warmup(engine) -> None:
    """Two short requests: a chunk alone, a chunk beside a decode, and
    decodes; the three step programs the window runs, at its shapes."""
    from repro.serving import Request

    page = engine.page_size
    reqs = [Request(rid=i, prompt=np.full((page + 1,), 3 + i, np.int32),
                    max_new_tokens=WARMUP_MAX_NEW, eos_id=-1)
            for i in range(2)]
    out = engine.serve(reqs)
    if any(len(out[r.rid]) != WARMUP_MAX_NEW for r in reqs):
        raise RuntimeError("warm-up requests did not finish")


def _step_table(engine, driver) -> list:
    start = {}
    for t, n in driver.steps:
        start[n] = t
    hist = {k: list(engine.metrics.histogram(f"engine.step_s.{k}").values)
            for k in ("decode", "chunk", "chunk+decode")}
    used = {k: 0 for k in hist}
    occ = engine.occupancy_log
    steps = []
    for i, entry in enumerate(engine.step_log):
        kind = ("decode" if not entry["prefill_in_flight"]
                else "chunk+decode" if entry["live_decode"] else "chunk")
        step_s = hist[kind][used[kind]] if used[kind] < len(hist[kind]) \
            else float("nan")
        used[kind] += 1
        steps.append({"t": start.get(i, float("nan")), "kind": kind,
                      "live": entry["live_decode"],
                      "pages_used": occ[i] if i < len(occ) else 0,
                      "step_s": step_s})
    return steps


def _records(engine, planned, driver) -> list:
    """One dict per request, from the engine's own stamps (first admission,
    every token) and the driver's due times."""
    stamps = engine.token_walltimes
    admits = engine.metrics.series("admit_walltime_s").by_key
    out = []
    for p in planned:
        rec = engine.results[p.rid]
        out.append({
            "rid": p.rid, "due": driver.t0 + p.due_s,
            "admit": admits[p.rid][0] if p.rid in admits else None,
            "stamps": list(stamps.get(p.rid, [])),
            "prompt_len": int(len(p.prompt)),
            "max_new": p.max_new_tokens,
            "state": rec.state.value,
            "tokens": list(rec.tokens),
        })
    return out


def check_sample(records: list, seed: int) -> list:
    """Requests the check reads: the one with most served tokens, and up to
    CHECK_REQUESTS - 1 others in an order drawn from the seed. Several
    requests, because one alone can fall into a loop of a few tokens whose
    margins no rounding disturbs."""
    cand = [r for r in records if len(r["tokens"]) >= 2
            and r["state"] in ("finished", "cancelled")]
    if not cand:
        return []
    cand.sort(key=lambda r: (-len(r["tokens"]), r["rid"]))
    rest = cand[1:]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 1])).permutation(len(rest))
    return [cand[0]] + [rest[i] for i in order[:CHECK_REQUESTS - 1]]


def logit_gaps(arch, weights, config, sample, prompts, max_len: int, *,
               precision: str = "float32") -> dict:
    """Gaps between the reference's best logit and its logit of the token
    judged, at every served token of ``sample``: the served token, or
    with ``precision="int8"`` the token that the int8 control puts first
    at that position. The reference is the architecture module's
    ``row_stats``. Returns the widest gap (``logit_gap``), the mean gap
    (``mean_logit_gap``) and the number of tokens read."""
    gaps = []
    for r in sample:
        served = np.asarray(r["tokens"], np.int32)
        prompt = prompts[r["rid"]]
        seq = np.concatenate([prompt, served[:-1]])
        nxt = np.concatenate([seq[1:], served[-1:]])
        rows = slice(len(prompt) - 1, len(seq))
        if precision == "float32":
            judged = nxt
        else:
            _, judged, _ = arch.row_stats(weights, config, seq,
                                          precision=precision, length=max_len)
        mx, _, picked = arch.row_stats(weights, config, seq, judged[None],
                                       length=max_len)
        gaps.append(mx[rows] - picked[0, rows])
    if not gaps:
        return {"logit_gap": None, "mean_logit_gap": None, "tokens": 0}
    g = np.concatenate(gaps)
    return {"logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
            "tokens": int(g.size)}


def prepare(cs: spec_mod.CellSpec, *, seed: int, t_start: float,
            mutate=None):
    """Weights from ``seed`` and a warm engine at the cell's sizes.
    ``mutate``, when given, is called with the built system before the
    warm-up (the tests break the timed path with it)."""
    from bench import sut

    system = sut.build_system(cs.arch, cs.config, cs.cell, seed)
    if mutate is not None:
        mutate(system)
    log(f"weights made: {time.perf_counter() - t_start:.1f} s")
    _warmup(system.engine)
    log(f"warm-up served: {time.perf_counter() - t_start:.1f} s")
    return system


@dataclasses.dataclass
class Window:
    ctx: Context
    prompts: dict            # rid -> prompt tokens
    late_s: list             # the generator's oversleeps


def serve_window(cs: spec_mod.CellSpec, system, *, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 out_dir: pathlib.Path,
                 rate_per_s: float | None = None) -> Window:
    """Serve the cell's traffic from ``seed`` through the window."""
    from repro.serving import Request

    config, cell, mix = cs.config, cs.cell, cs.traffic
    engine = system.engine
    rate = rate_per_s or cell.get("rate_per_s")
    n = cs.arrivals.count(mix, cell, seconds, rate)
    planned = generate.plan(mix, seed=seed, n=n, vocab=config["vocab_size"],
                            slots=engine.batch_size, rate_per_s=rate,
                            arrivals=cs.arrivals)
    requests = [Request(rid=p.rid, prompt=p.prompt,
                        max_new_tokens=p.max_new_tokens, eos_id=-1)
                for p in planned]
    trace_dir = pathlib.Path(out_dir) / "trace" / cs.name
    marks = {}

    def on_open(now):
        if trace:
            import jax

            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host annotations, no Python calls
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            marks["trace_open"] = time.perf_counter()

    ps = engine.page_size
    driver = OpenLoopDriver(
        {p.rid: p.due_s for p in planned}, seconds=seconds,
        backlog=not cs.arrivals.OPEN_LOOP, slots=engine.batch_size,
        pages={p.rid: -(-(len(p.prompt) + p.max_new_tokens) // ps)
               for p in planned},
        pool_pages=engine.num_pages - 1, on_open=on_open, annotate=trace)
    engine.injector = driver
    engine.serve(requests)
    driver.finish()
    if trace:
        import jax

        # stopped only now: writing the trace out takes seconds, which
        # would otherwise fall on requests still waiting in the drain
        jax.profiler.stop_trace()
    log(f"window served: {time.perf_counter() - t_start:.1f} s")
    if driver.w0 is None or driver.w1 is None:
        raise RuntimeError("the window never opened or never closed")
    ctx = Context(
        cell=cell, config=config, arch=cs.arch,
        end_to_end=tuple(m["name"] for m in cs.end_to_end),
        slots=engine.batch_size,
        pool_pages=engine.num_pages - 1, page_size=engine.page_size,
        chunk_size=engine.chunk_size,
        # a traced run's metrics read the traced part of the window
        w0=marks["trace_open"] if trace else driver.w0, w1=driver.w1,
        setup_s=driver.w0 - t_start,
        requests=_records(engine, planned, driver),
        steps=_step_table(engine, driver))
    if trace:
        from bench import trace_reduce

        tr = trace_reduce.load(trace_reduce.find_trace(trace_dir))
        offset = trace_reduce.host_offset_ns(
            tr, {n: t for t, n in driver.steps})
        span = None if offset is None else (
            int(marks["trace_open"] * 1e9) + offset,
            int(driver.w1 * 1e9) + offset)
        ctx.trace = trace_reduce.summarize(
            tr, window_ns=span,
            step_kinds={i: s["kind"] for i, s in enumerate(ctx.steps)})
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(ctx=ctx, prompts={p.rid: p.prompt for p in planned},
                  late_s=driver.late_s)


def request_checks(cs: spec_mod.CellSpec, ctx: Context):
    """(requests attempted, failed, finished short of their budget). With
    open-loop arrivals, a request due in the window that never got its
    first token failed too."""
    open_loop = cs.arrivals.OPEN_LOOP
    attempted = [r for r in ctx.requests if ctx.w0 <= r["due"] <= ctx.w1] \
        if open_loop else [r for r in ctx.requests if r["admit"] is not None]
    failed = [r for r in attempted if r["state"] == "failed"
              or (open_loop and not r["stamps"])]
    short = [r for r in ctx.requests if r["state"] == "finished"
             and len(r["tokens"]) != r["max_new"]]
    return attempted, failed, short


def judge(cs: spec_mod.CellSpec, gaps: dict, failed: list,
          short: list) -> dict:
    """The checks that decide ``correct``: each a number that may not pass
    its limit. The cell states a limit for each gap it compares (PERF.md
    gives the readings each limit was set from)."""
    checks = {name: {"value": gaps[name],
                     "limit": float(cs.cell[f"{name}_limit"])}
              for name in ("logit_gap", "mean_logit_gap")
              if f"{name}_limit" in cs.cell}
    checks["failed_requests"] = {"value": len(failed), "limit": 0}
    checks["short_requests"] = {"value": len(short), "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    """Every number compared is there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cs: spec_mod.CellSpec, *, seed: int, seconds: float,
             trace: bool, t_start: float, out_dir: pathlib.Path,
             chips: int = 1, mutate=None) -> dict:
    """One run of ``cs``; returns the result line as a dict."""
    system = prepare(cs, seed=seed, t_start=t_start, mutate=mutate)
    win = serve_window(cs, system, seed=seed, seconds=seconds, trace=trace,
                       t_start=t_start, out_dir=out_dir)
    ctx = win.ctx
    device = device_info(chips)
    if trace:
        ctx.peaks = spec_mod.peaks_for(device["kind"], cs.bench_dir)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        metric_list, kind = cs.per_layer, "layer_metrics"
    else:
        metric_list, kind = cs.end_to_end, "end_to_end"
    metrics = {}
    for m in metric_list:
        value = spec_mod.load_reader(cs.bench_dir, kind, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state goes before the reference runs
    system.engine = None
    gc.collect()
    attempted, failed, short = request_checks(cs, ctx)
    gaps = logit_gaps(cs.arch, system.weights, cs.config,
                      check_sample(ctx.requests, seed), win.prompts,
                      int(cs.cell["max_len"]))
    checks = judge(cs, gaps, failed, short)
    n_read = gaps["tokens"]
    log(f"reference checked {n_read} tokens: "
        f"{time.perf_counter() - t_start:.1f} s")
    result = {
        "correct": passes(checks),
        "attempted": len(attempted),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = ctx.trace.breakdown()
    result["generator_late_ms_max"] = (max(win.late_s) * 1e3
                                       if win.late_s else 0.0)
    result["tokens_checked"] = n_read
    result["checks"] = checks
    return result


def format_checks(checks: dict) -> list[str]:
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in checks.items()]
