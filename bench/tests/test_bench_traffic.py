"""The traffic generator and the open-loop driver, on the CPU at smoke
sizes: nothing is admitted before it is due, first-token time counts from
the due time, the window is cut by cancellation, and a seed gives the
same requests every time."""

import json
import time

import numpy as np
import pytest

from bench import harness, stats
from bench.tests.smoke_root import DATA, smoke_arch, smoke_config
from bench.traffic import generate
from bench.traffic.driver import OpenLoopDriver

SEED = 2**33 + 101


def _mix(name):
    return json.loads((DATA / name).read_text())


def test_same_seed_same_requests():
    mix = _mix("smoke_chat.json")
    a = generate.plan(mix, seed=SEED, n=20, vocab=512, rate_per_s=3.0)
    b = generate.plan(mix, seed=SEED, n=20, vocab=512, rate_per_s=3.0)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_seeds_share_the_lengths_and_gaps():
    """The mix fixes the schedule: two seeds give the same lengths in the
    same order at the same due times, with other token ids."""
    mix = _mix("smoke_chat.json")
    a = generate.plan(mix, seed=SEED, n=30, vocab=512, rate_per_s=3.0)
    b = generate.plan(mix, seed=SEED + 1, n=30, vocab=512, rate_per_s=3.0)
    assert [(len(r.prompt), r.max_new_tokens, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.due_s) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another schedule seed gives the same lengths and gaps, in another order
    c = generate.plan(dict(mix, schedule_seed=mix["schedule_seed"] + 1),
                      seed=SEED, n=30, vocab=512, rate_per_s=3.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    np.testing.assert_allclose(
        sorted(np.diff([0.0] + [r.due_s for r in a])),
        sorted(np.diff([0.0] + [r.due_s for r in c])))


def test_lengths_follow_the_mix():
    mix = _mix("smoke_chat.json")
    p = generate.plan(mix, seed=SEED, n=200, vocab=512, rate_per_s=3.0)
    lens = np.array([len(r.prompt) for r in p])
    assert lens.min() >= 4 and lens.max() <= 64
    assert abs(np.median(lens) - 24) <= 1
    # arrivals at the rate: n requests over about n / rate seconds
    assert abs(p[-1].due_s - 200 / 3.0) < 0.1 * 200 / 3.0


def test_residual_life_first_batch():
    mix = _mix("smoke_backlog.json")
    p = generate.plan(mix, seed=SEED, n=10, vocab=512, slots=4)
    assert all(r.due_s == 0.0 for r in p)
    # the first batch carries generated tokens in its prompt and has a
    # remainder to go; the totals stay within the mix's bounds
    for r in p[:4]:
        assert 1 <= r.max_new_tokens <= 64
        assert len(r.prompt) + r.max_new_tokens <= 32 + 64


@pytest.fixture(scope="module")
def smoke_engine():
    from bench import sut

    cell = json.loads((DATA / "smoke_cell.json").read_text())
    engine = sut.build_system(smoke_arch("smoke"), smoke_config("smoke"),
                              cell, seed=SEED, attn_impl="xla").engine
    harness._warmup(engine)  # nothing compiles in the tests' windows
    return engine


def _serve(engine, planned, **kw):
    from repro.serving import Request

    driver = OpenLoopDriver({p.rid: p.due_s for p in planned}, **kw)
    engine.injector = driver
    engine.serve([Request(rid=p.rid, prompt=p.prompt,
                          max_new_tokens=p.max_new_tokens, eos_id=-1)
                  for p in planned])
    driver.finish()
    return driver


def test_nothing_is_admitted_before_it_is_due(smoke_engine):
    mix = _mix("smoke_chat.json")
    planned = generate.plan(mix, seed=SEED, n=6, vocab=512, rate_per_s=6.0)
    driver = _serve(smoke_engine, planned, seconds=1.0, backlog=False,
                    slots=2)
    assert len(driver.admitted) == len(planned)
    for p in planned:
        assert driver.admitted[p.rid] >= driver.t0 + p.due_s
    # an idle engine slept to the next due time instead of spinning
    assert driver.late_s and max(driver.late_s) < 0.05
    assert driver.w1 - driver.w0 == pytest.approx(1.0, abs=0.05)


def test_ttft_counts_from_the_due_time(smoke_engine):
    mix = _mix("smoke_chat.json")
    planned = generate.plan(mix, seed=SEED, n=6, vocab=512, rate_per_s=6.0)
    # a stall before the first step holds the first request past its due
    # time: that wait is part of its first-token time
    slow = {"hit": False}

    class Slow(OpenLoopDriver):
        def step_begin(self, engine, step):
            super().step_begin(engine, step)
            if not slow["hit"]:
                slow["hit"] = True
                time.sleep(0.3)

    from repro.serving import Request

    driver = Slow({p.rid: p.due_s for p in planned}, seconds=1.0,
                  backlog=False, slots=2)
    smoke_engine.injector = driver
    smoke_engine.serve([Request(rid=p.rid, prompt=p.prompt,
                                max_new_tokens=p.max_new_tokens, eos_id=-1)
                        for p in planned])
    driver.finish()
    records = harness._records(smoke_engine, planned, driver)
    ctx = harness.Context(cell={}, config={}, arch=None, end_to_end=(),
                          slots=2, pool_pages=16, page_size=16, chunk_size=32,
                          w0=driver.w0,
                          w1=driver.w1, setup_s=0.0, requests=records,
                          steps=[])
    want = [r["stamps"][0] - (driver.t0 + p.due_s)
            for r, p in zip(records, planned) if ctx.in_window(r["due"])]
    assert stats.ttfts_s(ctx) == pytest.approx(want)
    assert want[0] > 0.2  # the stall shows in first-token time


def test_window_is_cut_by_cancel(smoke_engine):
    mix = _mix("smoke_backlog.json")
    planned = generate.plan(mix, seed=SEED, n=40, vocab=512, slots=2)
    driver = _serve(smoke_engine, planned, seconds=0.1, backlog=True,
                    slots=2)
    states = {r.state.value for r in smoke_engine.results.values()}
    assert "cancelled" in states and states <= {"cancelled", "finished"}
    # the window opened only once both slots were decoding
    first_two = [smoke_engine.token_walltimes[p.rid][0] for p in planned[:2]]
    assert driver.w0 >= max(first_two)
    last = max(t for ts in smoke_engine.token_walltimes.values() for t in ts)
    assert last <= driver.w1 + 1.0
