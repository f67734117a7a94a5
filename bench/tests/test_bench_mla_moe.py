"""The latent-attention expert architecture (``bench/archs/mla_moe.py``)
in the harness on the CPU at a smoke size, and the hand counts of its
step, its latent decode kernel and its held experts at Moonlight's
published sizes (``bench/configs/moonlight-16b-a3b.json``)."""

import json
import time
import types

import pytest

from bench import harness, spec
from bench.kernels import moe_experts, paged_mla_decode
from bench.tests.smoke_root import BENCH, make_root

ROOT = BENCH.parent
SEED = 2**33 + 11
CELL = "smoke-mla.backlog"

# Moonlight's layout at a size a CPU runs: one dense layer, then expert
# layers of 8 routed experts (4 held) with 2 shared, in float32, where
# the program serves the reference's best token
SMOKE_MLA = {
    "name": "smoke-mla", "source": "test", "model_type": "deepseek_v3",
    "arch": "mla_moe", "first_k_dense_replace": 1, "hidden_size": 64,
    "intermediate_size": 96, "kv_lora_rank": 32, "moe_intermediate_size": 32,
    "n_group": 1, "n_routed_experts": 4, "n_routed_experts_published": 8,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 16, "vocab_size": 512, "torch_dtype": "float32",
    "reduced": [],
}
PEER = "moonlight-16b-a3b.reasoning"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("mla_root"),
                     cells=("smoke.backlog",))
    (root / "bench/configs/smoke-mla.json").write_text(json.dumps(SMOKE_MLA))
    cell = json.loads((BENCH / "tests/data/smoke_cell.json").read_text())
    (root / f"bench/cells/{CELL}.json").write_text(json.dumps(cell))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "smoke-mla", "source": "test",
                             "file": "bench/configs/smoke-mla.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "smoke-mla",
                               "traffic": "smoke_backlog", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if PEER in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def test_smoke_mla_cell_is_correct(root):
    """The cell runs through the paged engine with the latent decode
    kernel and the held-expert layer, and the served tokens agree with
    the module's float32 reference; it reports the Moonlight cell's
    end-to-end metrics."""
    cs = spec.load_cell(CELL, root)
    assert cs.arch.__file__ == str(root / "bench/archs/mla_moe.py")
    r = harness.run_cell(cs, seed=SEED, seconds=1.5, trace=False,
                         t_start=time.perf_counter(),
                         out_dir=root / ".bench_out")
    assert r["correct"], r["checks"]
    assert r["tokens_checked"] > 0
    assert set(r["metrics"]) == {m["name"] for m in spec.load_cell(
        PEER, ROOT).end_to_end}
    assert r["attempted"] > 0 and r["failed"] == 0


def test_smoke_mla_control_is_not_correct(root):
    """On the window's checked requests the program passes the cell's
    limit (1e-4; float32 serves the reference's best token) and the int8
    control does not."""
    from bench import control

    cs = spec.load_cell(CELL, root)
    system = harness.prepare(cs, seed=SEED, t_start=time.perf_counter())
    out = control.readings(cs, system, SEED, 1.5, root / ".bench_out")
    assert out["program_correct"], out["checks"]
    assert not out["control_correct"], out["control_checks"]


def _moonlight():
    return json.loads((BENCH / "configs/moonlight-16b-a3b.json").read_text())


def test_matmul_flops_per_token_by_hand():
    """Per token, 2 operations a weight: 27 latent-attention layers of
    2048*16*192 (q) + 2048*576 (latent) + 16*128*512 (W_UK absorbed) +
    16*512*128 (W_UV) + 16*128*2048 (Wo) = 13,762,560; the dense layer's
    3*2048*11264 = 69,206,016; 26 expert layers of 2048*64 (router) +
    3*2048*2816 (shared) + 0.75 * 3*2048*1408 (held rows: 6 * 8/64)
    = 23,920,640."""
    mod = spec.load_module(BENCH, "archs", "mla_moe")
    cfg = _moonlight()
    want = 2 * (27 * 13_762_560 + 69_206_016 + 26 * 23_920_640)
    assert mod.matmul_flops_per_token(cfg) == want
    assert mod.attention_flops(cfg, 10) == 2 * 16 * (576 + 512) * 10 * 27
    assert mod.head_flops(cfg) == 2 * 2048 * 163840


def test_latent_decode_cost_by_hand():
    """A sequence of 65 keys on pages of 64: 2 pages of 64 latent rows of
    576 values, the query (16 x 576) and output (16 x 512) rows, 2 bytes
    each; 2*16*(576+512) operations a key."""
    flops, nbytes = paged_mla_decode.cost(_moonlight(), [65, 1], 64)
    assert flops == 2 * 16 * 1088 * 66
    assert nbytes == 2 * ((2 * 64 * 576 + 16 * 576 + 16 * 512)
                          + (64 * 576 + 16 * 576 + 16 * 512))


def test_expert_cost_by_hand():
    """100 tokens over 3 step programs: 75 expected held rows a layer of
    2*3*2048*1408 operations, and 8 experts' 3 matrices of 2048*1408
    bf16 per layer and program, over 26 expert layers."""
    flops, nbytes = moe_experts.cost(_moonlight(), 100, 3)
    assert flops == pytest.approx(2 * 3 * 2048 * 1408 * 75 * 26)
    assert nbytes == 3 * 8 * 3 * 2048 * 1408 * 2 * 26


def test_roofline_readers_count_only_their_kernels():
    """The two readers divide the counts above by their kernel family's
    device time, and find nothing to read in a dense cell."""
    from bench.layer_metrics import mla_decode_roofline as mla_r
    from bench.layer_metrics import moe_experts_roofline as moe_r

    cfg = _moonlight()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = types.SimpleNamespace(kernel_s=lambda name: {
        "paged_decode_attention": 0.01, "ragged-dot-none": 0.05}.get(name, 0.0))
    ctx = types.SimpleNamespace(
        trace=trace, config=cfg, peaks=peaks, page_size=64, chunk_size=1024,
        requests=[{"prompt_len": 64, "stamps": [1.0, 2.0, 3.0]}],
        in_window=lambda t: True,
        window_steps=lambda: [{"kind": "decode"}, {"kind": "decode"}])
    _, nbytes = paged_mla_decode.cost(cfg, [65, 66], 64)
    assert mla_r.read(ctx) == pytest.approx(
        100 * nbytes * 27 / 819e9 / 0.01)
    _, nbytes = moe_experts.cost(cfg, 2, 2)
    assert moe_r.read(ctx) == pytest.approx(100 * nbytes / 819e9 / 0.05)
    ctx.config = json.loads((BENCH / "configs/internlm2-1.8b.json")
                            .read_text())
    assert mla_r.read(ctx) is None and moe_r.read(ctx) is None
