"""Compile rehearsals for a described (not attached) TPU v5e.

The TPU compiler is installed even where no chip is, and it refuses what
the chip would refuse: block shapes Mosaic cannot tile, and programs that
do not fit the device's memory.  These tests compile the three Pallas
kernels and the chip smoke's inner step for ``v5e:2x2`` and run nothing.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro import models, optim
from repro.configs import get_config
from repro.core.diloco import make_inner_step, make_outer_step
from repro.core.switch import plan_execution
from repro.kernels.flash_attention.kernel import flash_attention_padded
from repro.kernels.gradstats.kernel import gradstats_padded
from repro.kernels.mamba_scan.kernel import mamba_scan_padded
from repro.launch.train import build_loss_fn

#: one v5e chip's HBM
HBM_BYTES = 16 * 2 ** 30


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
def topo(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _flash(spec):
    q = spec((2, 2048, 16, 128), jnp.bfloat16)     # qwen3-0.6b heads
    kv = spec((2, 2048, 8, 128), jnp.bfloat16)
    window = spec((1,), jnp.int32)
    return (lambda q, k, v, w: flash_attention_padded(
        q, k, v, w, interpret=False)), (q, kv, kv, window)


def _gradstats(spec):
    return (lambda G: gradstats_padded(G, interpret=False),
            (spec((8, 2 ** 20), jnp.float32),))


def _mamba(spec):
    cfg = get_config("falcon-mamba-7b")
    di, n, S = cfg.d_inner, cfg.ssm.state_dim, 1024
    u = spec((1, S, di), jnp.bfloat16)
    bc = spec((1, S, n), jnp.bfloat16)
    return (lambda u, dt, a, b, c: mamba_scan_padded(
        u, dt, a, b, c, interpret=False)), (u, u, spec((di, n), jnp.float32),
                                            bc, bc)


@pytest.mark.parametrize("build", [_flash, _gradstats, _mamba],
                         ids=["flash_attention", "gradstats", "mamba_scan"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b_req", [chip_smoke.SMOKE["initial_batch"],
                                   chip_smoke.SMOKE["max_global_batch"]],
                         ids=["plain", "accum"])
def test_smoke_inner_step_fits_one_v5e(one_chip, b_req):
    """The inner step of ``chip_smoke.py`` at its depth, sequence length
    and each plan it runs compiles for one v5e, and together with the
    rest of the trainer's device state fits the chip's HBM."""
    s = chip_smoke.SMOKE
    plan = plan_execution(b_req, s["max_batch"], s["switch_multiplier"])
    cfg = get_config(chip_smoke.ARCH).with_overrides(
        num_layers=chip_smoke.NUM_LAYERS)
    opt = optim.adamw(3e-4, weight_decay=0.1)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (plan.accum_steps, plan.micro_batch, chip_smoke.SEQ_LEN), jnp.int32,
        sharding=one_chip)}
    step = make_inner_step(build_loss_fn(cfg), opt, plan.accum_steps)
    compiled = step.lower(_shapes(params, one_chip),
                          _shapes(opt_state, one_chip), batch).compile()
    mem = compiled.memory_analysis()
    fresh = (mem.output_size_in_bytes - mem.alias_size_in_bytes
             + mem.temp_size_in_bytes)
    # the host dispatches the next step before this one finishes, so a
    # second step's fresh outputs and temporaries are live at once
    in_flight = mem.argument_size_in_bytes + 2 * fresh

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    n = sum(a.size for a in jax.tree.leaves(params))
    grad_bytes = n * (4 if plan.accum_steps > 1 else 2)
    # what stays on the device beside worker 1's steps: the trainer's
    # params and f32 outer momentum, worker 0's AdamW state, params and
    # last gradients
    resident = (nbytes(params) + 4 * n + nbytes(opt_state)
                + nbytes(params) + grad_bytes)
    assert in_flight + resident < HBM_BYTES, (in_flight, resident)


def _smoke_stats_compiled(one_chip):
    """The batch statistics after an accum round of ``chip_smoke.py``
    (two workers' f32 gradients), compiled for one v5e, and the
    parameter shapes."""
    from repro.core import batching

    cfg = get_config(chip_smoke.ARCH).with_overrides(
        num_layers=chip_smoke.NUM_LAYERS)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0)))
    grads = _shapes(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params),
        one_chip)
    return batching.stats_from_microbatch_grads.lower(
        [grads, grads], micro_size=chip_smoke.SMOKE["max_batch"]
    ).compile(), params


def test_smoke_microbatch_stats_fit_one_v5e(one_chip):
    """The batch statistics after an accum round of ``chip_smoke.py``
    (two workers' f32 gradients) compile as one program that fits beside
    the trainer's state: params, f32 outer momentum, two AdamW states
    and two workers' params."""
    compiled, params = _smoke_stats_compiled(one_chip)
    mem = compiled.memory_analysis()
    stats_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    n = sum(a.size for a in jax.tree.leaves(params))
    resident = n * (2 + 4 + 2 * 8 + 2 * 2)
    assert stats_bytes + resident < HBM_BYTES, (stats_bytes, resident)


def test_smoke_microbatch_stats_read_each_gradient_once(one_chip):
    """The batch statistics of ``chip_smoke.py``'s two workers' f32
    gradients make no gradient-sized copy and read each tree once:
    temporaries under 1/16 of the (2, D) f32 matrix, bytes accessed
    under 1.5x one read of the two trees (a second pass over them
    would read 2x)."""
    compiled, params = _smoke_stats_compiled(one_chip)
    two_trees = 2 * 4 * sum(a.size for a in jax.tree.leaves(params))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < two_trees / 16, (temp, two_trees)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    accessed = cost["bytes accessed"]
    assert accessed < 1.5 * two_trees, (accessed, two_trees)


def test_smoke_outer_step_reads_each_worker_once(one_chip):
    """The outer step of ``chip_smoke.py``'s two workers, their bf16
    params passed as a tuple, compiles for one v5e as one pass: no
    temporaries, and bytes accessed under 1.4x one read of its arguments
    and one write of its outputs (the step over a stacked worker axis
    accessed 1.6x)."""
    cfg = get_config(chip_smoke.ARCH).with_overrides(
        num_layers=chip_smoke.NUM_LAYERS)
    opt = optim.get_optimizer("nesterov", 0.7, momentum=0.9)
    params = jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(0)))
    p = _shapes(params, one_chip)
    compiled = make_outer_step(opt).lower(
        p, (p, p), _shapes(jax.eval_shape(opt.init, params), one_chip),
        0.0).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes == 0, mem.temp_size_in_bytes
    once = mem.argument_size_in_bytes + mem.output_size_in_bytes
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    accessed = cost["bytes accessed"]
    assert accessed < 1.4 * once, (accessed, once)
