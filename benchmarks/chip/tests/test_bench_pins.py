"""Each cell's tiny cut at one seed, pinned: a digest of the weights
``make_weights`` makes, and the float32 reference's first round (every
loss, the batch statistics, and each per-entry norm of the first
gradients, the changes and the outer step), to 6 significant digits.
A change that moves the benchmark's weights or its reference fails here.

Each cell's pins are a file of their own, ``testdata/pins/<cell>.json``,
so a cell is added with its pins as a new file.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python3 -m \\
        benchmarks.chip.tests.test_bench_pins <cell>

writes that cell's file, and no other.
"""
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.tiny import tiny_cell

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 33 + 12345
WRITE = "PYTHONPATH=src:. JAX_PLATFORMS=cpu python3 -m " \
        "benchmarks.chip.tests.test_bench_pins"


def pins_file(name: str, root: Path = harness.ROOT) -> Path:
    return root / "benchmarks" / "chip" / "testdata" / "pins" / f"{name}.json"


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


def pins(name: str, root: Path = harness.ROOT) -> dict:
    cell = tiny_cell(name, root)
    return {"weights_sha256": weights_digest(cell),
            "first_round": first_round(cell)}


def check_pinned(name: str, root: Path = harness.ROOT) -> None:
    path = pins_file(name, root)
    if not path.is_file():
        pytest.fail(f"no pins for the cell {name!r} ({path}); write them "
                    f"with: {WRITE} {name}")
    assert pins(name, root) == json.loads(path.read_text())


@pytest.mark.parametrize("name", CELLS)
def test_weights_and_reference_first_round_are_pinned(name):
    check_pinned(name)


def test_a_cell_without_its_pins_fails_naming_the_command(tmp_path):
    with pytest.raises(pytest.fail.Exception,
                       match=f"{WRITE} qwen3-0.6b.absent"):
        check_pinned("qwen3-0.6b.absent", tmp_path)


def write(name: str, root: Path = harness.ROOT) -> Path:
    """Write the pins of the cell ``name`` alone."""
    path = pins_file(name, root)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(pins(name, root), indent=1) + "\n")
    return path


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(f"usage: {WRITE} <cell>")
    print(write(sys.argv[1]))
