"""Compile rehearsal for a described (not attached) TPU v5e: the
stablelm-1.6b cut's inner step and batch statistics, at the cell's plan,
compile for one chip and fit its HBM beside the trainer's state, with
two steps in flight.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.chip import harness

#: one v5e chip's HBM
HBM_BYTES = 16 * 2 ** 30
CELL = "stablelm-1.6b.switch-2x4"


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cut():
    from repro import models
    cell = harness.load_cell(CELL)
    cfg = harness.program_config(cell.config)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0)))
    return cell, cfg, params


def _shapes(tree, sharding, dtype=None):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, dtype or a.dtype, sharding=sharding), tree)


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_stablelm_inner_step_fits_one_v5e(one_chip, cut):
    from repro import optim
    from repro.core.diloco import make_inner_step
    from repro.launch.train import build_loss_fn

    cell, cfg, params = cut
    plan, t = cell.traffic["plan"], cell.traffic
    opt = optim.adamw(t["lr_inner"], weight_decay=t["weight_decay"])
    opt_state = jax.eval_shape(opt.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (plan["accum_steps"], plan["micro_batch"], t["seq_len"]), jnp.int32,
        sharding=one_chip)}
    step = make_inner_step(build_loss_fn(cfg), opt, plan["accum_steps"])
    mem = step.lower(_shapes(params, one_chip), _shapes(opt_state, one_chip),
                     batch).compile().memory_analysis()
    fresh = (mem.output_size_in_bytes - mem.alias_size_in_bytes
             + mem.temp_size_in_bytes)
    # the host dispatches the next step before this one finishes
    in_flight = mem.argument_size_in_bytes + 2 * fresh
    n = sum(a.size for a in jax.tree.leaves(params))
    # beside worker 1's steps: the trainer's params and f32 outer
    # momentum, worker 0's AdamW state, end params and f32 gradients
    resident = (_nbytes(params) + 4 * n + _nbytes(opt_state)
                + _nbytes(params) + 4 * n)
    assert in_flight + resident < HBM_BYTES, (in_flight, resident)


def test_stablelm_microbatch_stats_fit_one_v5e(one_chip, cut):
    from repro.core import batching

    cell, _, params = cut
    grads = _shapes(params, one_chip, jnp.float32)
    mem = batching.stats_from_microbatch_grads.lower(
        [grads, grads], micro_size=cell.traffic["plan"]["micro_batch"]
    ).compile().memory_analysis()
    stats_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    n = sum(a.size for a in jax.tree.leaves(params))
    # params and f32 outer momentum, two AdamW states, two workers' params
    resident = n * (2 + 4 + 2 * 8 + 2 * 2)
    assert stats_bytes + resident < HBM_BYTES, (stats_bytes, resident)
