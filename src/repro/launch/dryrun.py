import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver.

Lowers + compiles every runnable (architecture x input shape) cell on the
production meshes (16x16 single pod; 2x16x16 multi-pod) and records
memory analysis, cost analysis, and the collective-traffic breakdown per
cell as JSON under experiments/dryrun/<mesh>/<arch>__<shape>.json.

The XLA_FLAGS line above MUST stay the first statement: jax locks the
host device count at first backend initialization.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both|tiny] [--out DIR]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import SHAPES, all_cells, cell_is_runnable, get_arch  # noqa: E402
from repro.distributed.ctx import sharding_policy  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh, make_test_mesh  # noqa: E402
from repro.launch.steps import cell_lowering_inputs  # noqa: E402
from repro.analysis.hlo import collective_bytes_from_hlo  # noqa: E402


def run_cell(arch_id: str, shape_id: str, mesh, mesh_name: str) -> dict:
    cell = SHAPES[shape_id]
    t0 = time.time()
    step, args, donate, policy = cell_lowering_inputs(arch_id, cell, mesh)
    with jax.set_mesh(mesh), sharding_policy(mesh, policy):
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    mem_d = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_d[f] = int(getattr(mem, f, 0) or 0)
    cost_d = {}
    if cost:
        c = cost if isinstance(cost, dict) else cost[0]
        for k in ("flops", "bytes accessed", "transcendentals",
                  "utilization operand 0 {}", "bytes accessed output {}"):
            if k in c:
                cost_d[k.replace(" ", "_").replace("{}", "").strip("_")] = (
                    float(c[k])
                )
    coll = collective_bytes_from_hlo(compiled.as_text())
    result = {
        "arch": arch_id,
        "shape": shape_id,
        "mesh": mesh_name,
        "policy": policy,
        "num_devices": mesh.devices.size,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_d,
        "cost": cost_d,
        "collectives": coll,
        "ok": True,
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both", "tiny"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multipod2x16x16", make_production_mesh(multi_pod=True)))
    if args.mesh == "tiny":
        meshes.append(("tiny2x4", make_test_mesh(8)))

    n_ok = n_fail = n_skip = 0
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch_id, shape_id, runnable, why in all_cells():
            if args.arch and arch_id != args.arch:
                continue
            if args.shape and shape_id != args.shape:
                continue
            path = os.path.join(outdir, f"{arch_id}__{shape_id}.json")
            if not runnable:
                with open(path, "w") as f:
                    json.dump({"arch": arch_id, "shape": shape_id,
                               "mesh": mesh_name, "ok": False,
                               "skipped": True, "reason": why}, f, indent=1)
                print(f"[skip] {mesh_name} {arch_id} {shape_id}: {why}",
                      flush=True)
                n_skip += 1
                continue
            try:
                res = run_cell(arch_id, shape_id, mesh, mesh_name)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                print(
                    f"[ ok ] {mesh_name} {arch_id} {shape_id}: "
                    f"compile={res['compile_s']}s "
                    f"flops/dev={res['collectives']['flops_corrected']:.3e} "
                    f"coll={res['collectives']['total_bytes']:.3e}B",
                    flush=True,
                )
                n_ok += 1
            except Exception as e:  # noqa: BLE001
                n_fail += 1
                with open(path, "w") as f:
                    json.dump({"arch": arch_id, "shape": shape_id,
                               "mesh": mesh_name, "ok": False,
                               "error": repr(e)}, f, indent=1)
                print(f"[FAIL] {mesh_name} {arch_id} {shape_id}: {e!r}",
                      flush=True)
                traceback.print_exc()
                if args.fail_fast:
                    raise
    print(f"dryrun done: ok={n_ok} fail={n_fail} skip={n_skip}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
