"""§5.6 reproduction: maximum supportable sequence length, MAS vs FLAT.

The paper: on the 5 MB-L1 edge device in fp16, MAS handles ~1 M tokens
(two row buffers must coexist: P_i plus C_{i+1} or P_{i-1}) while FLAT
handles ~2 M (one row buffer). We sweep N and report the largest
feasible length for each dataflow under the §4.3 capacity rules, plus
the TPU-side analogue from core.policy: the N at which the policy leaves
K/V-resident MAS for streamed MAS, and the N at which the paper's
dataflow yields to the online-softmax kernel, at the VMEM budget the MAS
kernel is compiled with (E=128, bf16).
"""

from __future__ import annotations

from repro.sim import EDGE_HW
from repro.sim.schedules import Tiling, build_schedule
from repro.sim.workload import AttentionWorkload

from repro.core.policy import DEFAULT_VMEM_BUDGET, choose_attention_method


def _feasible(method: str, n: int, hw=EDGE_HW, emb: int = 64,
              nkv: int = 256) -> bool:
    """Single-row (hh=1, nq=1) §4.3 capacity rules — closed form of the
    checks in sim.schedules (building million-task graphs just to test
    capacity would be silly)."""
    bpe = hw.bytes_per_elem
    rb = n * bpe                      # one (1 x N) row buffer
    qo = 4 * emb * bpe
    kv_tile = nkv * emb * bpe
    if method == "mas":               # two row buffers must coexist
        return 2 * rb + qo <= hw.l1_bytes
    return rb + 4 * kv_tile + qo <= hw.l1_bytes  # flat: one buffer


def max_len(method: str, hw=EDGE_HW) -> int:
    lo, hi = 1, 2
    while _feasible(method, hi, hw) and hi < 2**27:
        lo, hi = hi, hi * 2
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if _feasible(method, mid, hw) else (lo, mid)
    return lo


def first_n(left: tuple[str, ...], budget: int = DEFAULT_VMEM_BUDGET,
            step: int = 512) -> int:
    """Smallest N (a multiple of ``step``) whose policy decision is not in
    ``left``; the decisions move in one direction as N grows."""

    def past(n):
        d = choose_attention_method(n_kv=n, e=128, itemsize=2,
                                    vmem_budget=budget)
        return d.method not in left

    lo, hi = step, step
    while not past(hi):
        lo, hi = hi, hi * 2
    while hi - lo > step:
        mid = (lo + hi) // 2 // step * step
        lo, hi = (lo, mid) if past(mid) else (mid, hi)
    return hi


def run():
    mas_n = max_len("mas")
    flat_n = max_len("flat")
    return {
        "mas_max_seq": mas_n,
        "flat_max_seq": flat_n,
        "ratio_flat_over_mas": flat_n / mas_n,
        "paper": {"mas": 1_000_000, "flat": 2_000_000, "ratio": 2.0},
        "tpu_vmem_budget": DEFAULT_VMEM_BUDGET,
        "tpu_resident_to_streamed_at": first_n(("mas_resident",)),
        "tpu_mas_to_flash_at": first_n(("mas_resident", "mas_streamed")),
    }


def main(emit):
    r = run()
    emit("seq_limit/mas_max", 0.0, f"N={r['mas_max_seq']:,} (paper ~1M)")
    emit("seq_limit/flat_max", 0.0, f"N={r['flat_max_seq']:,} (paper ~2M)")
    emit("seq_limit/ratio", 0.0,
         f"flat/mas={r['ratio_flat_over_mas']:.2f} (paper 2.0)")
    mib = r["tpu_vmem_budget"] // 2**20
    emit("seq_limit/tpu_resident_handoff", 0.0,
         f"resident->streamed at N={r['tpu_resident_to_streamed_at']:,} "
         f"({mib}MiB VMEM)")
    emit("seq_limit/tpu_policy_handoff", 0.0,
         f"MAS->flash at N={r['tpu_mas_to_flash_at']:,} ({mib}MiB VMEM)")
    return r
