"""Benchmark harness — one module per paper table/figure (+ roofline).

  PYTHONPATH=src python -m benchmarks.run             # full
  PYTHONPATH=src python -m benchmarks.run --quick
  PYTHONPATH=src python -m benchmarks.run --only fig1

Emits ``name,us_per_call,derived`` CSV.

  fig1     convergence.py        AdLoCo vs DiLoCo (paper Fig. 1)
  fig2     ablations.py          component ablations (paper Fig. 2)
  thm1     batch_growth.py       E[b_k] = Omega(k)  (Theorem 1)
  thm2     comm_complexity.py    E[C(N)] = O(ln N)  (Theorem 2)
  kernel   kernels_bench.py      Pallas kernels vs jnp oracle
  roofline roofline_table.py     dry-run roofline baselines (40 pairs x 2 meshes)
  cluster  cluster_bench.py      sync vs async vs elastic on simulated hardware
  serve    serve_bench.py        dense vs paged continuous batching under traffic
"""
from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    ("fig1", "benchmarks.convergence"),
    ("fig2", "benchmarks.ablations"),
    ("thm1", "benchmarks.batch_growth"),
    ("thm2", "benchmarks.comm_complexity"),
    ("kernel", "benchmarks.kernels_bench"),
    ("roofline", "benchmarks.roofline_table"),
    ("cluster", "benchmarks.cluster_bench"),
    ("serve", "benchmarks.serve_bench"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    choices=[k for k, _ in MODULES])
    args = ap.parse_args(argv)

    import importlib

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for key, modname in MODULES:
        if args.only and key != args.only:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            for r in mod.run(quick=args.quick):
                print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"",
                      flush=True)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            print(f"{key}/ERROR,0.0,\"{type(e).__name__}: {e}\"", flush=True)
        print(f"# {key} done in {time.time() - t0:.1f}s", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
