"""CPU tests of the chip entry points: the training body of
``chip_smoke.py`` at a reduced size, its refusal to run anywhere but on a
TPU or outside a checkout, and where the compile cache goes."""
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.configs import get_config, reduced

ROOT = Path(chip_smoke.__file__).resolve().parent


def test_smoke_training_body_runs_plain_then_accum_at_reduced_size():
    cfg = reduced(get_config(chip_smoke.ARCH))
    lines = []
    hist, plans = chip_smoke.train_smoke(cfg, seq_len=32, log=lines.append,
                                         **chip_smoke.SMOKE)
    assert [p.mode for p in plans][0] == "plain"
    assert "accum" in [p.mode for p in plans]
    assert all(math.isfinite(x) for x in hist.loss)
    assert len(lines) == chip_smoke.SMOKE["rounds"]


def _run(cmd, cwd, **env):
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env={**os.environ, **env})


def test_smoke_refuses_the_cpu_and_names_it():
    r = _run([sys.executable, "chip_smoke.py"], ROOT, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    for args in ([], ["--four-chips"]):
        r = _run([sys.executable, "chip_smoke.py", *args], tmp_path,
                 JAX_PLATFORMS="cpu", PYTHONPATH="")
        assert r.returncode != 0
        assert '"ok"' not in r.stdout


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_location(tmp_path, env_dir):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says, else to the
    checkout's .jax_cache; a compile lands there."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()\n"
        "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env={**env, "PYTHONPATH": str(ROOT / "src")})
    assert r.returncode == 0, r.stderr
    path, configured = json.loads(r.stdout.strip().splitlines()[-1])
    want = (tmp_path / "cache") if env_dir else ROOT / ".jax_cache"
    assert Path(path) == want
    assert Path(configured) == want
    assert any(want.iterdir())
