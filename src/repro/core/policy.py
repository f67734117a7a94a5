"""Static memory policy — the TPU analogue of the paper's §4.3 guard.

The paper's proactive overwrite is a *runtime* guard: when softmax output
P_i would overflow L1, MAS evicts the reloadable K/V operand and reloads it
later. On TPU, DMA is software-scheduled, so the same policy is decided
*ahead of time* from static shapes: given a VMEM budget, choose

  kv_resident  — K and V pinned in VMEM (paper's ideal regime),
  streamed     — K/V tiles overwritten per step and V re-fetched per Q-row
                 block (the overwrite/reload regime; DRAM reads inflate
                 exactly like §5.4.2),
  flash        — online softmax (beyond-paper): when even one (blk_q, N)
                 fp32 score row cannot be held, the paper's dataflow is
                 infeasible (its §5.6 sequence-length limitation) and we
                 fall through to the optimized kernel.

Returned decisions also carry the estimated VMEM working set so callers
(and the autotuner) can reason about footprints without recompiling.
"""

from __future__ import annotations

import dataclasses

# Usable VMEM for one kernel's working set. v5e has 128 MiB of VMEM per
# core; the MAS kernel asks the compiler for exactly this much scoped VMEM
# (mas_attention_flat's ``vmem_limit_bytes``), so the budget planned here
# and the limit compiled against are one number.
DEFAULT_VMEM_BUDGET = 64 * 2**20


@dataclasses.dataclass(frozen=True)
class TilingConfig:
    """The paper's tiling factors, TPU-shaped.

    blk_q  = N_Q   (query rows per block; MXU sublane dim, multiple of 8)
    blk_kv = N_KV  (key/value rows per sub-tile; MXU lane dim, mult. of 128)
    """

    blk_q: int = 128
    blk_kv: int = 512
    kv_resident: bool = True

    def __post_init__(self):
        assert self.blk_q >= 1 and self.blk_kv >= 1


@dataclasses.dataclass(frozen=True)
class PolicyDecision:
    method: str  # "mas_resident" | "mas_streamed" | "flash"
    tiling: TilingConfig
    vmem_bytes: int
    reason: str
    # Causal workloads prune fully-masked KV tiles in every kernel variant
    # (DESIGN.md §3); the decision carries the flag so downstream cost
    # models (autotune._score) charge the pruned workload, not the dense one.
    causal: bool = False


def _bytes(n_elems: int, itemsize: int) -> int:
    return n_elems * itemsize


def sublane_rows(itemsize: int) -> int:
    # Smallest Q block the kernels tile: fp32 -> 8, bf16 -> 16, int8 -> 32
    # rows (ops.attention rounds blk_q up to this).
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def mas_vmem_bytes(
    blk_q: int, blk_kv: int, n: int, e: int, itemsize: int,
    kv_resident: bool,
) -> int:
    """VMEM the MAS kernel needs, counted as Mosaic allocates it.

    * the fp32 (blk_q, N) score row buffer (Alg. 3), plus one fp32 row
      temporary of the same size that the row softmax holds beside it;
    * every input and output block twice: Pallas double-buffers each
      BlockSpec, resident K/V included;
    * two fp32 (blk_q, blk_kv) score-tile temporaries (matmul result and
      its mask).

    N is padded to whole ``blk_kv`` tiles, as ops.attention pads K/V.
    tests/test_tpu_compile.py checks the count against the v5e compiler.
    """
    n = -(-n // blk_kv) * blk_kv
    rows = 2 * _bytes(blk_q * n, 4)
    tiles = 2 * _bytes(blk_q * blk_kv, 4)
    q_o = 2 * 2 * _bytes(blk_q * e, itemsize)
    if kv_resident:
        kv = 2 * 2 * _bytes(n * e, itemsize)  # K + V pinned, double-buffered
        acc = 0  # accumulates via fori carry (vregs)
    else:
        kv = 2 * 2 * _bytes(blk_kv * e, itemsize)  # K, V tiles
        acc = _bytes(blk_q * e, 4)
    return rows + tiles + q_o + kv + acc


def flash_vmem_bytes(blk_q: int, blk_kv: int, e: int, itemsize: int) -> int:
    tiles = 2 * _bytes(blk_q * e, itemsize) + 4 * _bytes(blk_kv * e, itemsize)
    scratch = _bytes(blk_q * (e + 2), 4)
    out = 2 * _bytes(blk_q * e, itemsize)
    return tiles + scratch + out


def choose_attention_method(
    *,
    n_kv: int,
    e: int,
    itemsize: int = 2,
    tiling: TilingConfig | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    prefer: str = "auto",
    causal: bool = False,
) -> PolicyDecision:
    """Pick the kernel variant for a given attention workload.

    ``prefer`` forces a method ("mas", "flash") or "auto" applies the
    paper-ordered policy: resident -> streamed (overwrite) -> flash.
    ``causal`` does not change feasibility (the row buffer still spans the
    full N) but is threaded into the decision so cost models charge the
    pruned tile set.
    """
    t = tiling or TilingConfig()
    blk_kv = min(t.blk_kv, n_kv)
    blk_q = t.blk_q

    if prefer == "flash":
        return PolicyDecision(
            "flash", TilingConfig(blk_q, blk_kv, False),
            flash_vmem_bytes(blk_q, blk_kv, e, itemsize),
            "forced flash", causal,
        )

    resident = mas_vmem_bytes(blk_q, blk_kv, n_kv, e, itemsize, True)
    if resident <= vmem_budget:
        return PolicyDecision(
            "mas_resident", TilingConfig(blk_q, blk_kv, True), resident,
            f"K/V ({2 * n_kv * e * itemsize} B) + row buffer fit VMEM",
            causal,
        )

    streamed = mas_vmem_bytes(blk_q, blk_kv, n_kv, e, itemsize, False)
    if streamed <= vmem_budget:
        return PolicyDecision(
            "mas_streamed", TilingConfig(blk_q, blk_kv, False), streamed,
            "K/V evicted per tile (proactive overwrite); row buffer fits",
            causal,
        )

    # Shrink blk_q before giving up on the paper's dataflow — the paper
    # shrinks N_Q the same way for long sequences (§5.6).
    bq = blk_q
    while bq > sublane_rows(itemsize):
        bq //= 2
        streamed = mas_vmem_bytes(bq, blk_kv, n_kv, e, itemsize, False)
        if streamed <= vmem_budget:
            return PolicyDecision(
                "mas_streamed", TilingConfig(bq, blk_kv, False), streamed,
                f"row buffer fits after shrinking blk_q to {bq}", causal,
            )

    if prefer == "mas":
        raise ValueError(
            f"MAS dataflow infeasible: the fp32 score rows of n_kv={n_kv} "
            f"need {mas_vmem_bytes(bq, blk_kv, n_kv, e, itemsize, False)} B "
            f"> budget {vmem_budget} B (paper §5.6)"
        )
    return PolicyDecision(
        "flash", TilingConfig(blk_q, blk_kv, False),
        flash_vmem_bytes(blk_q, blk_kv, e, itemsize),
        "paper dataflow infeasible at this N (§5.6) — online softmax",
        causal,
    )
