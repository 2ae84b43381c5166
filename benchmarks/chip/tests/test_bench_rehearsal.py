"""Each cell's set-up, first round, window and check at a size the CPU
runs, through the harness's own functions; the precision control and
every fault the cells can have make ``correct`` false; the command
refuses to run off the TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import calibrate, harness
from benchmarks.chip.tests.tiny import tiny_cell

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 33 + 12345          # wider than 32 bits, as run seeds may be


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    cell = tiny_cell(request.param)
    prog = harness.build_and_first_round(cell, SEED, 64,
                                         harness.Norms(cell.family))
    win = harness.run_window(prog, 0.5)
    ref = harness.reference_readings(cell, SEED, prog.feeds)
    return cell, prog, win, harness.compare(prog.readings, ref)


def test_round_loop_runs_and_is_correct(sound):
    cell, prog, win, numbers = sound
    t = cell.traffic
    assert win.attempted >= 1 and win.failed == 0 and win.error is None
    assert win.end_s > 0 and len(win.round_s) >= 1
    assert prog.names["inner"][1] == t["inner_steps"] * t["workers"]
    assert prog.names["stats"] == ("jit_stats_from_microbatch_grads", 1)
    # rows the window took came from the pre-drawn feed
    assert all(f.taken for f in prog.feeds)
    ok, check = harness.judge(numbers, cell.limits)
    assert ok, check
    assert set(check) == set(cell.limits)


def test_whole_round_cell_checks_stats_and_outer(sound):
    """Every cell's check follows the whole first round: each worker's
    every step, the batch statistics and the outer step."""
    cell, prog, _, numbers = sound
    t = cell.traffic
    assert {"stats", "outer"} <= set(numbers)
    r = prog.readings
    assert len(r.losses) == t["inner_steps"] * t["workers"]
    assert len(r.grad) == len(r.change) == t["workers"]
    assert r.stats is not None and r.outer is not None


@pytest.mark.parametrize("name", CELLS)
def test_precision_control_is_not_correct(name):
    cell = tiny_cell(name)
    ok, check = harness.judge(calibrate.control(harness, cell, SEED),
                              cell.limits)
    assert not ok, check


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_under_the_timed_step_is_not_correct(name, fault):
    """Each fault, the one on a single worker included, fails a number."""
    cell = tiny_cell(name)
    numbers = calibrate.sound_or_fault(harness, cell, SEED,
                                       calibrate.FAULTS[fault])
    ok, check = harness.judge(numbers, cell.limits)
    assert not ok, check


def _bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu_and_names_it():
    r = _bench(harness.ROOT)
    assert r.returncode != 0
    assert "'cpu'" in r.stdout + r.stderr
    assert not r.stdout.strip().endswith("}")


def test_command_fails_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to measure."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert not r.stdout.strip().endswith("}")


def test_check_holds_the_numbers_its_limits_name():
    """A zero mean gradient gives no noise ratio; a number without a
    limit is left out of the check."""
    norms = {"w": np.ones(2)}
    r = harness.Readings([1.0], [norms], [norms],
                         {"sigma2": 2.0, "mean_norm2": 1.0}, norms, (0,))
    zero = r._replace(stats={"sigma2": 0.0, "mean_norm2": 0.0})
    numbers = harness.compare(zero, r)
    assert numbers["stats"] == 1.0 and numbers["stats_ratio"] == np.inf
    ok, check = harness.judge(numbers, {"loss": 0.1, "stats": 2.0})
    assert ok and set(check) == {"loss", "stats"}
    assert not harness.judge(numbers, {"loss": 0.1, "stats_ratio": 0.3})[0]
