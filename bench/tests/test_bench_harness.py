"""The harness end to end on the CPU at smoke sizes (the look for a chip
skipped): a cell added as new files alone runs, and a timed path broken
underneath comes out not correct. Plus the command's refusals: no TPU,
and a checkout without the program."""

import filecmp
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, spec
from bench.traffic import generate
from bench.tests.smoke_root import BENCH, make_root

ROOT = BENCH.parent
SEED = 2**33 + 7


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def _run(root, cell, mutate=None, seconds=1.5):
    cs = spec.load_cell(cell, root)
    return harness.run_cell(cs, seed=SEED, seconds=seconds, trace=False,
                            t_start=time.perf_counter(),
                            out_dir=root / ".bench_out", mutate=mutate)


def test_copied_tree_is_unchanged(root):
    """The smoke cell is new files only: every file of bench/ is there,
    byte for byte."""
    for path in BENCH.rglob("*"):
        rel = path.relative_to(BENCH)
        if path.is_file() and "tests" not in rel.parts \
                and "__pycache__" not in rel.parts:
            assert filecmp.cmp(path, root / "bench" / rel, shallow=False), rel


@pytest.mark.parametrize("cell,peer", [
    ("smoke.chat", "qwen3-1.7b.chat"),
    ("smoke.backlog", "internlm2-1.8b.reasoning"),
])
def test_new_cell_runs_from_new_files(root, cell, peer):
    """A cell added as files runs, and reports the end-to-end metrics of
    the cell whose traffic kind it shares."""
    r = _run(root, cell)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in spec.load_cell(peer, ROOT).end_to_end}
    assert set(r["metrics"]) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] == 1 and r["device"]["platform"] == "cpu"


def test_new_architecture_runs_from_new_files(root):
    """A configuration of another architecture (the program's MoE block)
    added as files alone: its module ``bench/archs/smoke_moe.py``, its
    configuration and a cell. The cell runs through the harness, and the
    served tokens agree with that module's reference."""
    assert not (BENCH / "archs/smoke_moe.py").exists()
    cs = spec.load_cell("smoke-moe.backlog", root)
    assert cs.arch.__file__ == str(root / "bench/archs/smoke_moe.py")
    r = _run(root, "smoke-moe.backlog")
    assert r["correct"], r["checks"]
    assert r["tokens_checked"] > 0
    assert set(r["metrics"]) == {m["name"] for m in spec.load_cell(
        "internlm2-1.8b.reasoning", ROOT).end_to_end}
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("arch", [None, "no_such_arch"])
def test_load_cell_refuses_a_configuration_without_its_architecture(
        tmp_path, arch):
    """A configuration that names no architecture, or one with no module,
    is an error that names the configuration's file: never a default."""
    root = make_root(tmp_path, cells=("smoke.chat",))
    path = root / "bench/configs/smoke.json"
    config = json.loads(path.read_text())
    if arch is None:
        del config["arch"]
    else:
        config["arch"] = arch
    path.write_text(json.dumps(config))
    with pytest.raises((KeyError, FileNotFoundError), match="smoke.json"):
        spec.load_cell("smoke.chat", root)


def test_new_arrival_kind_runs_from_new_files(root):
    """A kind of arrivals added as a module file (bursts of three) and a
    mix that names it: the cell runs, its requests come in bursts, and the
    harness treats its load as open-loop, as the module says."""
    assert not (BENCH / "traffic/arrivals/bursty.py").exists()
    cs = spec.load_cell("smoke.bursty", root)
    planned = generate.plan(cs.traffic, seed=SEED, n=9, vocab=512,
                            rate_per_s=3.0, arrivals=cs.arrivals)
    due = [p.due_s for p in planned]
    assert due[0] == due[1] == due[2] < due[3] == due[4] == due[5]
    r = _run(root, "smoke.bursty")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {
        m["name"] for m in spec.load_cell("qwen3-1.7b.chat", ROOT).end_to_end}
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("cell", ["smoke.backlog", "smoke-moe.backlog"])
def test_control_is_not_correct(root, cell):
    """The control goes through the harness's own judgement and the cell's
    architecture module: on the window's checked requests the program
    passes the cell's limits and the int8 control, put in its place, does
    not. (Both smoke configurations are float32, where the program serves
    the reference's best token: the limit, 1e-4, lies between the
    program's readings, 0, and the control's on the backlog: 1.2e-3 to
    8.3e-3 dense over twelve seeds, 1.7e-3 to 2.8e-2 MoE over six.)"""
    from bench import control

    cs = spec.load_cell(cell, root)
    system = harness.prepare(cs, seed=SEED, t_start=time.perf_counter())
    out = control.readings(cs, system, SEED, 1.5, root / ".bench_out")
    assert out["program_correct"], out["checks"]
    assert not out["control_correct"], out["control_checks"]


def _cache_unchanged(system):
    """Every step returns the pool it was given: no K/V row is kept."""
    eng = system.engine

    def unchanged(step):
        return lambda p, c, *a: (step(p, c, *a)[0], c)

    eng._decode = unchanged(eng._decode)
    eng._chunk_step = unchanged(eng._chunk_step)
    eng._chunk_only = unchanged(eng._chunk_only)


def _on_decoding_steps(system, change):
    """Apply ``change(tokens)`` to the decode tokens of every step that
    decodes (decode alone, and chunk+decode: both return the B decode
    tokens first)."""
    eng = system.engine

    def wrap(step):
        def broken(p, c, *a):
            out, c = step(p, c, *a)
            return change(out), c
        return broken

    eng._decode = wrap(eng._decode)
    eng._chunk_step = wrap(eng._chunk_step)


def _token_altered(system):
    """Every decode token is replaced where it is produced."""
    b, vocab = system.engine.batch_size, system.engine.cfg.vocab_size
    _on_decoding_steps(system, lambda out: out.at[:b].set((out[:b] + 1)
                                                          % vocab))


def _half_batch(system):
    """Each step decodes half the slots; the other half get copies of the
    first half's tokens."""
    b = system.engine.batch_size
    _on_decoding_steps(system, lambda out: out.at[b // 2:b].set(
        out[:b - b // 2]))


# The half-batch fault runs on the backlog, which keeps every slot live:
# at the smoke chat's load a second slot is live in too few steps for the
# four checked requests to meet it on every run.
@pytest.mark.parametrize("cell,fault", [
    ("smoke.chat", _cache_unchanged), ("smoke.chat", _token_altered),
    ("smoke.backlog", _cache_unchanged), ("smoke.backlog", _token_altered),
    ("smoke.backlog", _half_batch),
])
def test_broken_timed_path_is_not_correct(root, cell, fault):
    r = _run(root, cell, mutate=fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


def test_command_refuses_a_machine_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert "{" not in p.stdout


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-1.7b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_json_names_every_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cs = spec.load_cell(w["name"], ROOT)
        assert cs.config["name"] == w["config"]
        for m in cs.end_to_end:
            spec.load_reader(cs.bench_dir, "end_to_end", m["name"])
        for m in cs.per_layer:
            spec.load_reader(cs.bench_dir, "layer_metrics", m["name"])
    for c in bench["configs"]:
        arch = json.loads((ROOT / c["file"]).read_text())["arch"]
        assert (BENCH / "archs" / f"{arch}.py").exists(), c["file"]
    with pytest.raises(KeyError):
        spec.peaks_for("no such chip")
