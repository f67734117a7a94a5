"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. One process holds the chip: it loads,
warms up, measures for ``--seconds`` and prints one JSON line last on
standard output (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``:
each number the correctness check compared, beside its limit). The same
checks are the last lines on standard error. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer ones, read from a device trace of the window.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for: there is no CPU fallback.
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
OUT_DIR = ROOT / ".bench_out"


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """Exit non-zero unless JAX sees ``n`` TPU chips or more."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench: needs {n} TPU chip(s); JAX found {len(devs)} "
                 f"{devs[0].platform} device(s)")


def main(argv=None) -> int:
    args = _parse(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness, spec

    cs = spec.load_cell(args.workload, ROOT)
    require_chips(cs.chips)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    result = harness.run_cell(cs, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              out_dir=OUT_DIR, chips=cs.chips)
    for line in harness.format_checks(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
