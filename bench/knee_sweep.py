"""Sweep the arrival rate of a Poisson cell to find its knee, on the chip.

    python3 bench/knee_sweep.py --workload qwen3-1.7b.chat \\
        --rates 0.2,0.4,0.6,0.8 --seconds 51 --seed 5

One process, one warm engine, one window per rate (the cell's traffic
with its rate replaced). For each rate it prints one JSON line: requests
due in the window, completed requests per second, output tokens per
second, the first-token and inter-token tails, and the queue left at the
window's close (requests due but not yet admitted). The knee is the
highest rate whose queue stays short and whose late requests wait no
longer than its early ones; the cell's rate is set at about four fifths
of it, by hand, in ``bench/cells/<cell>.json``. Speed only: the output
check of ``bench/run.py`` is not made here.
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


def sweep_line(ctx, rate: float) -> dict:
    from bench.stats import itls_s, percentile, ttfts_s, window_tokens

    due = sorted((r for r in ctx.requests if ctx.in_window(r["due"])),
                 key=lambda r: r["due"])
    ttft = ttfts_s(ctx)
    third = max(1, len(due) // 3)

    def med_ttft(rs):
        return percentile([(r["stamps"][0] - r["due"]) if r["stamps"]
                           else float("inf") for r in rs], 50)

    return {
        "rate_per_s": rate, "requests": len(due),
        "completed_per_s": sum(r["state"] == "finished"
                               and r["stamps"][-1] <= ctx.w1
                               for r in ctx.requests) / ctx.window_s,
        "output_tok_s": window_tokens(ctx) / ctx.window_s,
        "ttft_p50_ms": percentile(ttft, 50) * 1e3,
        "ttft_p90_ms": percentile(ttft, 90) * 1e3,
        "ttft_p95_ms": percentile(ttft, 95) * 1e3,
        "itl_p95_ms": (percentile(itls_s(ctx), 95) or 0.0) * 1e3,
        "ttft_first_third_p50_ms": med_ttft(due[:third]) * 1e3,
        "ttft_last_third_p50_ms": med_ttft(due[-third:]) * 1e3,
        "queue_at_close": sum(r["admit"] is None or r["admit"] > ctx.w1
                              for r in due),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache

    from bench import harness, spec
    from bench.run import require_chips

    cs = spec.load_cell(args.workload, ROOT)
    require_chips(cs.chips)
    enable_compile_cache()
    system = harness.prepare(cs, seed=args.seed, t_start=T_START)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        win = harness.serve_window(
            cs, system, seed=args.seed + i, seconds=args.seconds,
            trace=False, t_start=T_START, out_dir=ROOT / ".bench_out",
            rate_per_s=rate)
        print(json.dumps(sweep_line(win.ctx, rate)), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
