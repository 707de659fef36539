"""Each fault a training cell can have, planted under the timed path of
a run at a size the CPU holds, comes out as not correct; a sound run and
the float8 control come out as the limits say."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench_paths import BENCH, REPO

import chip_harness
import correctness
from chipbench_tiny import LIMITS, run, tiny_cell

CELL = "smollm-135m.train.1chip"


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The test process keeps JAX's compile cache as it found it."""
    monkeypatch.setattr(chip_harness, "use_compile_cache", lambda: None)


def test_sound_run_is_correct():
    result, numbers = run(tiny_cell(CELL))
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    for name, c in result["checks"].items():
        assert c["limit"] == LIMITS[name] and c["value"] <= c["limit"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    def unchanged(step_fn, pshard, oshard):
        j = jax.jit(step_fn, out_shardings=(pshard, oshard, None))

        def step(params, opt_state, batch):
            _, _, metrics = j(params, opt_state, batch)
            return params, opt_state, metrics
        return step
    monkeypatch.setattr(chip_harness, "jit_step", unchanged)
    result, numbers = run(tiny_cell(CELL))
    assert result["correct"] is False
    # the first weights are made again to take the change: 1 to rounding
    assert numbers["update_gap"] == pytest.approx(1.0, abs=1e-4)


def test_half_of_the_batch_left_out(monkeypatch):
    real = chip_harness.jit_step

    def half(step_fn, pshard, oshard):
        j = real(step_fn, pshard, oshard)

        def step(params, opt_state, batch):
            rows = batch["tokens"]
            return j(params, opt_state, {"tokens": rows[: len(rows) // 2]})
        return step
    monkeypatch.setattr(chip_harness, "jit_step", half)
    result, numbers = run(tiny_cell(CELL))
    assert result["correct"] is False
    assert numbers["grad_gap"] > 10 * LIMITS["grad_gap"]


def test_float8_control_fails():
    """The control (the reference in float8, in the program's place)
    against the float32 reference."""
    cell = tiny_cell(CELL)
    prog = chip_harness.build_program(cell)
    import token_generator
    pool = token_generator.pool_for(cell.traffic, prog.cfg.vocab, 5)
    key = chip_harness.weight_key(5)
    ref = chip_harness.reference_record(cell, prog, pool, key)
    ctl = chip_harness.reference_record(cell, prog, pool, key,
                                        precision="fp8")
    numbers = correctness.compare(ctl, ref)
    ok, _ = correctness.judge(numbers, LIMITS)
    assert not ok
    # it fails the full-size cell's gradient limit as well
    assert numbers["grad_gap"] > chip_harness.load_cell(CELL).limits[
        "grad_gap"]


def _clean_env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_no_chip_no_result():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_clean_env(), cwd=REPO,
        timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has no program to run."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_clean_env(), cwd=tmp_path,
        timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip()
