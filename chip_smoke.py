"""Chip smoke test: AdLoCo training end to end on a TPU, through the
normal entry points, with a check of what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # k=2 trainers x M=2 workers on four
                                       # chips, one process per chip

One chip: ``qwen3-0.6b`` at its published widths (d_model 1024, 16 query
and 8 KV heads of 128, d_ff 3072, vocab 151,936, bf16), cut in depth to
fit one v5e's 16 GB with one trainer of M=2 workers, each holding AdamW
state.  Random weights from a seed, synthetic token streams from the same
seed.  It checks the bf16 loss of the initial weights against a float32
reference, then runs ``repro.core.train_adloco`` with adaptive batching
and switch mode for a few outer rounds, and fails unless every loss is
finite and the run executed a plain plan and then a switch-mode
``accum`` plan.

Four chips: ``python -m repro.cluster.launch_mp --procs 4 --k 2 --merge
--check`` (grouped outer syncs and a cross-group merge on real
collectives, checked for parity against the in-process ``SimBackend``);
this process touches JAX only after those workers have exited.

Either way the script exits non-zero unless JAX's platform is ``tpu``,
and the last line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "qwen3-0.6b"
#: depth cut, sized from ``memory_analysis()`` of the inner step compiled
#: for a v5e: at 8 layers (281M parameters) one trainer with two AdamW
#: workers, the outer momentum and two steps in flight peaked at 13.5 GB
#: of the 16.9 GB a v5e chip lets a program allocate
NUM_LAYERS = 8
SEQ_LEN = 1024

#: training settings: round 1 runs a plain batch of 2; the strict norm
#: test (small eta) then asks for more than ``max_batch``, so the next
#: rounds accumulate micro-batches of 2 (switch mode, multiplier 1), up
#: to the global cap of 8
SMOKE = dict(rounds=3, inner_steps=2, initial_batch=2, max_batch=2,
             switch_multiplier=1, max_global_batch=8, eta=0.1, seed=0)

#: |bf16 loss - f32 loss| allowed on the initial weights: bf16 keeps 8
#: bits of mantissa (relative rounding 2^-9 per operation), and the loss
#: near ln(151936) ~ 11.9 stays well inside 1% after 8 layers; a wrong
#: kernel or layout moves it by whole nats
LOSS_RTOL = 1e-2


def train_smoke(cfg, *, seq_len, rounds, inner_steps, initial_batch,
                max_batch, switch_multiplier, max_global_batch, eta, seed,
                log=print):
    """One AdLoCo trainer of two workers on ``cfg``, the way
    ``repro.launch.train`` drives it.  Returns ``(history, plans)``: the
    ``History`` of ``train_adloco`` and the ``ExecutionPlan`` each round
    ran.  Raises if a loss is not finite or the run never switched from a
    plain plan to an ``accum`` plan."""
    import jax

    from repro import models
    from repro.configs.base import AdLoCoConfig
    from repro.core import train_adloco
    from repro.core.switch import plan_execution
    from repro.data import make_shard_streams
    from repro.launch.train import build_loss_fn

    acfg = AdLoCoConfig(
        num_outer_steps=rounds, num_inner_steps=inner_steps,
        lr_inner=3e-4, num_init_trainers=1, nodes_per_gpu=2,
        initial_batch_size=initial_batch, max_batch=max_batch,
        switch_multiplier=switch_multiplier,
        max_global_batch=max_global_batch, eta=eta,
        stats_estimator="microbatch", adaptive=True, enable_switch=True,
        enable_merge=False, seed=seed)
    params = models.init_params(cfg, jax.random.PRNGKey(seed))
    streams = make_shard_streams(cfg.vocab_size, seq_len, 2, seed=seed)
    _, hist = train_adloco(build_loss_fn(cfg), [params], streams, acfg)

    requested = [initial_batch] + [b[0] for b in hist.requested_batches]
    plans = [plan_execution(b, max_batch, switch_multiplier)
             for b in requested[:rounds]]
    ends = [0.0] + hist.wall
    for t, plan in enumerate(plans):
        log(f"round {t + 1}: loss={hist.loss[t]!r} "
            f"plan=({plan.micro_batch}x{plan.accum_steps}, {plan.mode}) "
            f"next_batch={requested[t + 1]} "
            f"round_wall_s={ends[t + 1] - ends[t]!r} "
            f"(host clock, informational, compiles included)")
    modes = [m[0] for m in hist.modes]
    if modes != [p.mode for p in plans]:
        raise RuntimeError(f"executed modes {modes} disagree with the "
                           f"plans of the requested batches {plans}")
    if not all(math.isfinite(x) for x in hist.loss):
        raise RuntimeError(f"non-finite loss: {hist.loss}")
    if "plain" not in modes or "accum" not in modes[modes.index("plain"):]:
        raise RuntimeError(f"expected a plain plan and then an accum plan, "
                           f"ran {modes}")
    return hist, plans


def reference_check(cfg, *, seed, log=print):
    """The bf16 loss of ``cfg``'s seeded initial weights on one small
    batch, against the same loss in float32 at the highest matmul
    precision.  Raises if they disagree by more than ``LOSS_RTOL``."""
    import jax
    import jax.numpy as jnp

    from repro import models

    params = models.init_params(cfg, jax.random.PRNGKey(seed))
    batch = models.example_batch(cfg, 2, SEQ_LEN)
    loss = float(jax.jit(lambda p, b: models.loss_fn(p, b, cfg)[0])(
        params, batch))
    cfg32 = cfg.with_overrides(dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    del params
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: models.loss_fn(p, b, cfg32)[0])(
            params32, batch))
    log(f"initial loss: bf16={loss!r} f32 reference={ref!r} "
        f"ln(vocab)={math.log(cfg.vocab_size)!r}")
    if not (math.isfinite(loss) and abs(loss - ref) <= LOSS_RTOL * abs(ref)):
        raise RuntimeError(f"bf16 loss {loss} disagrees with the f32 "
                           f"reference {ref} (rtol {LOSS_RTOL})")


def _device_line(jax) -> str:
    devs = jax.devices()
    return json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}})


def _require_tpu(jax) -> None:
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's platform "
                         f"is {platform!r}")


def one_chip() -> int:
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    import jax

    from repro.configs import get_config

    _require_tpu(jax)
    full = get_config(ARCH)
    cfg = full.with_overrides(num_layers=NUM_LAYERS)
    print(f"reduced: num_layers {full.num_layers} -> {cfg.num_layers}")
    print(f"arch={cfg.name} params={cfg.param_count()!r} seq_len={SEQ_LEN} "
          f"k=1 M=2 {SMOKE}")
    reference_check(cfg, seed=SMOKE["seed"])
    train_smoke(cfg, seq_len=SEQ_LEN, **SMOKE)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')!r} "
          f"bytes_limit={stats.get('bytes_limit')!r}")
    print(_device_line(jax))
    return 0


def four_chips() -> int:
    """``launch_mp`` with four worker processes, one chip each; JAX is
    imported here only after they have all exited."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p])
    cmd = [sys.executable, "-m", "repro.cluster.launch_mp", "--procs", "4",
           "--k", "2", "--rounds", "6", "--merge", "--check"]
    print("running: " + " ".join(cmd[1:]), flush=True)
    rc = subprocess.run(cmd, env=env, cwd=ROOT, timeout=900).returncode
    if rc != 0:
        print(f"chip_smoke: launch_mp exited {rc}", file=sys.stderr)
        return rc
    import jax

    _require_tpu(jax)
    print(_device_line(jax))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-process launch_mp path and its "
                         "SimBackend parity check")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {SRC}; run "
                         f"it from a checkout of the repository")
    if args.four_chips:
        return four_chips()
    sys.path.insert(0, str(SRC))
    return one_chip()


if __name__ == "__main__":
    sys.exit(main())
