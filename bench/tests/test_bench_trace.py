"""Trace reduction on a small trace recorded on a TPU v5e chip (the smoke
cell's window: a few engine steps of a 2-layer model, with the harness's
step annotations), and on hand-made intervals."""

import pathlib

import pytest

from bench import trace_reduce as tr

TRACE = pathlib.Path(__file__).resolve().parent / "data" / \
    "smoke_window.xplane.pb"


def test_union_merges_and_clips():
    ivs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 45, 60)]
    assert tr.union(ivs, 2, 50) == [(2, 20), (30, 40), (45, 50)]
    assert list(tr.gaps(tr.union(ivs, 2, 50), 2, 50)) == [(20, 30), (40, 45)]
    assert list(tr.gaps([], 0, 5)) == [(0, 5)]


def test_op_family_reads_hlo_text():
    assert tr.op_family("%fusion.123 = bf16[2]{0} fusion(...)") == "fusion"
    assert tr.op_family("%paged_decode_attention.9 = bf16[16,8,16,128] "
                        "custom-call(...)") == "paged_decode_attention"
    assert tr.op_family("%copy-start.1 = (s32[3]) copy-start()") == \
        "copy-start"


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def test_recorded_trace_has_device_ops_and_annotations(trace):
    assert list(trace.ops) == [0]
    families = {tr.op_family(n) for n, _, _ in trace.ops[0]}
    assert {"while", "paged_decode_attention",
            "paged_prefill_attention"} <= families
    assert any(n.startswith("bench_step") for n, _, _ in trace.host)
    assert any(n == tr.WAIT_ANN for n, _, _ in trace.host)


def test_summary_of_recorded_trace(trace):
    s = tr.summarize(trace)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    # the scan's loop holds the other ops: out of the breakdown
    assert "while" not in s.op_s
    assert s.kernel_s("paged_decode_attention") > 0
    assert s.kernel_s("paged_prefill_attention") > 0
    # the ops inside the loops add up to no more than the busy time
    assert sum(s.op_s.values()) <= s.busy_s * 1.0001
    b = s.breakdown()
    assert len(b["device_ops"]) <= tr.TOP and len(b["idle_gaps"]) <= tr.TOP
    labels = " ".join(n for n, _ in b["idle_gaps"])
    assert "waits for the next arrival" in labels
    assert "inside an engine step" in labels
    total_idle = sum(v for _, v in s.idle_gaps)
    assert total_idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def test_window_and_host_offset(trace):
    steps = [(n, a) for n, a, _ in trace.host if n.startswith("bench_step")]
    # host clock = trace clock - 5 s: the offset comes back exactly
    perf = {int(n.split()[1]): (a - 5_000_000_000) * 1e-9 for n, a in steps}
    off = tr.host_offset_ns(trace, perf)
    assert off == pytest.approx(5_000_000_000, abs=1000)
    lo = steps[0][1]
    s = tr.summarize(trace, window_ns=(lo, lo + 100_000_000))
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s <= 0.1
