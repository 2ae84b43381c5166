"""Readings that the limits of a cell's check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        [--seeds 12] [--control-seeds 3] [--fault-seeds 3] [--out DIR]

On the chip, at the cell's own sizes, in one process:

* sound: the program's first round against the reference on each seed,
  as a run of ``bench.py`` compares them (the lower readings);
* control: the reference computed through ``float8_e4m3fn`` put in the
  program's place, against the float32 reference (the upper readings);
* faults planted under the program's step: its parameters returned
  unchanged, the second worker's alone returned unchanged, half of each
  batch left out, its loss altered by 1%.

Prints one JSON line per reading and writes them all to
``<out>/<cell>.json``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def state_unchanged(fn, worker):
    def step(params, opt_state, batch):
        _, opt_state, loss, grads = fn(params, opt_state, batch)
        return params, opt_state, loss, grads
    return step


def worker_state_unchanged(fn, worker):
    """The second worker's parameters returned unchanged, the first
    worker's step untouched."""
    return state_unchanged(fn, worker) if worker == 1 else fn


def half_batch(fn, worker):
    """Half of each batch's rows left out (half of each row where a
    micro-batch holds one row), the mean taken over the rest."""
    def step(params, opt_state, batch):
        tok = batch["tokens"]
        half = (tok[:, :tok.shape[1] // 2] if tok.shape[1] > 1
                else tok[:, :, :tok.shape[2] // 2])
        return fn(params, opt_state, {"tokens": half})
    return step


def loss_altered(fn, worker):
    def step(params, opt_state, batch):
        params, opt_state, loss, grads = fn(params, opt_state, batch)
        return params, opt_state, loss * 1.01, grads
    return step


FAULTS = {"state_unchanged": state_unchanged,
          "worker_state_unchanged": worker_state_unchanged,
          "half_batch": half_batch, "loss_altered": loss_altered}


def sound_or_fault(harness, cell, seed, fault=None):
    norms = harness.Norms()
    prog = harness.build_and_first_round(
        cell, seed, harness.rows_for(cell, 0.0, 0.0), norms, fault)
    readings, feeds = prog.readings, prog.feeds
    del prog
    gc.collect()
    return harness.compare(readings, harness.reference_readings(
        cell, seed, feeds))


def control(harness, cell, seed):
    import jax.numpy as jnp

    from benchmarks.chip import reference as R
    feeds = harness.feeds_for(cell, seed, harness.rows_for(cell, 0.0, 0.0))
    # the rows each batch would take, as the program's first round takes
    # them
    t = cell.traffic
    per = t["plan"]["micro_batch"] * t["plan"]["accum_steps"]
    for f in feeds:
        for _ in range(t["inner_steps"]):
            f.taken.append((len(f.taken) * per, per))
    low = harness.reference_readings(cell, seed, feeds,
                                     R.round_trip(jnp.float8_e4m3fn))
    gc.collect()
    return harness.compare(low, harness.reference_readings(cell, seed, feeds))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="calibration")
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    harness.require_chips(jax, cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = []

    def emit(kind, seed, numbers, t0):
        row = {"kind": kind, "seed": seed, "numbers": numbers,
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for s in seeds:
        t0 = time.perf_counter()
        emit("sound", s, sound_or_fault(harness, cell, s), t0)
    for s in seeds[:args.control_seeds]:
        t0 = time.perf_counter()
        emit("control", s, control(harness, cell, s), t0)
    for name, wrap in FAULTS.items():
        for s in seeds[:args.fault_seeds]:
            t0 = time.perf_counter()
            emit(name, s, sound_or_fault(harness, cell, s, wrap), t0)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
