"""Each cell's tiny cut at one seed, pinned: a digest of the weights
``make_weights`` makes, and the float32 reference's first round (every
loss, the batch statistics, and each per-entry norm of the first
gradients, the changes and the outer step), to 6 significant digits.
A change that moves the benchmark's weights or its reference fails here.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python3 -m \\
        benchmarks.chip.tests.test_bench_pins

writes the pins anew (``testdata/first_round_pins.json``).
"""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import tiny_cell

PINS = Path(__file__).resolve().parents[1] / "testdata" / \
    "first_round_pins.json"
CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 33 + 12345


def _sig(values) -> list:
    return [float(f"{v:.6g}") for v in np.ravel(values)]


def _entries(norms: dict) -> dict:
    return {k: _sig(v) for k, v in sorted(norms.items())}


def weights_digest(cell) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.weights import make_weights

    fam = cell.family
    w = make_weights(fam.layout(fam.arch_of(cell.config)), SEED,
                     jnp.bfloat16)
    h = hashlib.sha256()
    for kp, x in jax.tree_util.tree_flatten_with_path(w)[0]:
        x = np.asarray(x)
        h.update(f"{harness._path(kp)} {x.dtype} {x.shape}".encode())
        h.update(x.tobytes())
    return h.hexdigest()


def first_round(cell) -> dict:
    """The reference's first-round readings on the rows the program's
    first round would take."""
    t = cell.traffic
    per = t["plan"]["micro_batch"] * t["plan"]["accum_steps"]
    feeds = harness.feeds_for(cell, SEED, t["inner_steps"] * per)
    for f in feeds:
        for _ in range(t["inner_steps"]):
            f.next_batch(per)
    r = harness.reference_readings(cell, SEED, feeds)
    return {"losses": _sig(r.losses),
            "stats": {k: _sig(v)[0] for k, v in sorted(r.stats.items())},
            "grad": [_entries(g) for g in r.grad],
            "change": [_entries(c) for c in r.change],
            "outer": _entries(r.outer)}


def pins(name: str) -> dict:
    cell = tiny_cell(name)
    return {"weights_sha256": weights_digest(cell),
            "first_round": first_round(cell)}


@pytest.mark.parametrize("name", CELLS)
def test_weights_and_reference_first_round_are_pinned(name):
    want = json.loads(PINS.read_text())[name]
    assert pins(name) == want


if __name__ == "__main__":
    PINS.write_text(json.dumps({n: pins(n) for n in CELLS}, indent=1) + "\n")
    sys.exit(0)
