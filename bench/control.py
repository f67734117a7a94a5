"""Readings of the output check: the program's and the int8 control's.

    python3 bench/control.py --workload qwen3-1.7b.chat --seconds 51 \\
        --seeds 1,2,3,...

For each seed, in one process on the chip: weights from the seed, the
cell's window at the cell's load (as ``bench/run.py`` serves it), then
on the requests that a run checks, the gaps between the float32
reference's best logit and its logit of (a) the token the program
served and (b) the token that the reference computed in int8 (the
control) puts first; the reference is the cell's architecture module's
(``bench/archs/``). Both go through the harness's own judgement against
the cell's limits: ``program_correct`` has to come out true and
``control_correct`` false. One JSON line per seed. The cell's limits are
set between the program's largest readings and the control's smallest,
as ``PERF.md`` records. The benchmark's own runs do not run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(cs, system, seed: int, seconds: float, out_dir) -> dict:
    """One seed's program and control readings on a prepared system."""
    from bench import harness, sut

    if system.weights is None:
        system.engine.params = None
        system.weights = sut.make_weights(cs.arch, cs.config, seed)
        system.engine.params = cs.arch.program_params(system.weights,
                                                      cs.config)
    win = harness.serve_window(cs, system, seed=seed, seconds=seconds,
                               trace=False, t_start=time.perf_counter(),
                               out_dir=out_dir)
    sample = harness.check_sample(win.ctx.requests, seed)
    max_len = int(cs.cell["max_len"])
    program = harness.logit_gaps(cs.arch, system.weights, cs.config, sample,
                                 win.prompts, max_len)
    control = harness.logit_gaps(cs.arch, system.weights, cs.config, sample,
                                 win.prompts, max_len, precision="int8")
    _, failed, short = harness.request_checks(cs, win.ctx)
    checks = harness.judge(cs, program, failed, short)
    control_checks = harness.judge(cs, control, failed, short)
    served = [t for r in sample for t in r["tokens"]]
    repeats = sum(a == b for r in sample
                  for a, b in zip(r["tokens"], r["tokens"][1:]))
    out = {"seed": seed, "tokens": program["tokens"],
           "requests": len(sample), "distinct_tokens": len(set(served)),
           "repeat_share": repeats / max(1, len(served) - len(sample)),
           "program_correct": harness.passes(checks),
           "control_correct": harness.passes(control_checks)}
    for name in ("logit_gap", "mean_logit_gap"):
        out[f"program_{name}"] = program[name]
        out[f"control_{name}"] = control[name]
    out["checks"] = checks
    out["control_checks"] = control_checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache

    from bench import harness, spec
    from bench.run import require_chips

    cs = spec.load_cell(args.workload, ROOT)
    require_chips(cs.chips)
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    system = harness.prepare(cs, seed=seeds[0], t_start=T_START)
    for i, seed in enumerate(seeds):
        if i:
            system.weights = None  # made anew from this seed
        print(json.dumps(readings(cs, system, seed, args.seconds,
                                  ROOT / ".bench_out")), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
