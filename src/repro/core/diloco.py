"""DiLoCo primitives: jitted inner step (with SwitchMode gradient
accumulation) and outer step (Nesterov on averaged pseudo-gradients).

These are the device-side building blocks; orchestration (trainer pool,
merging, batch adaptation) lives in ``adloco.py``.  A ``StepCache``
memoizes compiled steps per (micro_batch, accum_steps) bucket so adaptive
batching doesn't thrash XLA.
"""
from __future__ import annotations

import operator
from functools import reduce
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro import optim
from repro.core.switch import ExecutionPlan


def make_inner_step_fn(loss_fn: Callable, inner_opt: optim.Optimizer,
                       accum_steps: int):
    """Unjitted inner-step builder (the launcher jits it with explicit
    shardings/donation; ``make_inner_step`` jits it for host use).

    fn(params, opt_state, batch) -> (params, opt_state, loss, grads).
    ``batch`` leaves are shaped (accum_steps, micro, ...); accumulation is
    a ``lax.scan`` so the HLO stays O(1) in accum_steps (SwitchMode's
    device-side face).  The returned ``grads`` is the mean gradient the
    update used — reused by the distributed batching-stats estimator.
    For accum_steps == 1 the f32 accumulation buffer is skipped (grads
    stay in param dtype — matters for the 314B configs' memory budget).
    """

    def inner_step(params, opt_state, batch):
        mb = jax.tree.map(lambda x: x[0], batch)
        with jax.named_scope("grad"):
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb)
        with jax.named_scope("update"):
            updates, opt_state = inner_opt.update(grads, opt_state, params)
            params = optim.apply_updates(params, updates)
        return params, opt_state, loss, grads

    def inner_step_accum(params, opt_state, batch):
        def micro_grad(carry, mb):
            g_acc, l_acc = carry
            with jax.named_scope("grad"):
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + loss), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g_sum, l_sum), _ = jax.lax.scan(micro_grad, (g0, jnp.float32(0.0)),
                                         batch)
        inv = 1.0 / accum_steps
        grads = jax.tree.map(lambda g: g * inv, g_sum)
        with jax.named_scope("update"):
            updates, opt_state = inner_opt.update(grads, opt_state, params)
            params = optim.apply_updates(params, updates)
        return params, opt_state, l_sum * inv, grads

    return inner_step if accum_steps == 1 else inner_step_accum


def make_inner_step(loss_fn: Callable, inner_opt: optim.Optimizer,
                    accum_steps: int):
    # The optimizer state is donated: every caller replaces it with the
    # step's output, and without donation two steps in flight hold four
    # copies of it (AdamW at 0.3B parameters: 9 GB of a 16 GB chip).  The
    # params are not: the orchestrator reuses x_start across the M
    # workers and the outer step.
    return jax.jit(make_inner_step_fn(loss_fn, inner_opt, accum_steps),
                   donate_argnums=(1,))


def make_outer_step(outer_opt: optim.Optimizer, *,
                    delay_aware: bool = False):
    """jitted fn(x_prev, worker_params, outer_state, delay)
    -> (x_new, outer_state).

    ``worker_params`` is a sequence of M param-shaped pytrees: the
    workers' params, or a backend's already-reduced mean as a 1-tuple.
    Pseudo-gradient Δ = x_prev − (Σ_m x_m) / M in f32 (paper Alg 3 line
    42), formed inside the program, so each worker's leaf is read
    straight into the delta and the update and no worker-stacked copy
    exists; in a multi-host deployment the mean is the inter-worker
    all-reduce this framework meters as communication.  ``delay`` is the
    measured staleness (rounds folded between snapshot and application,
    f32 scalar): with ``delay_aware=True`` it is forwarded to the
    optimizer's ``update`` (``optim.delay_compensated_nesterov``), which
    scales the momentum contribution accordingly; otherwise it is
    ignored, keeping the plain path bit-identical to the legacy step.
    """

    def worker_mean(*ws):
        return reduce(operator.add,
                      [w.astype(jnp.float32) for w in ws]) / len(ws)

    def outer_step(x_prev, worker_params, outer_state, delay=0.0):
        with jax.named_scope("grad"):
            delta = jax.tree.map(
                lambda xp, *ws: xp.astype(jnp.float32) - worker_mean(*ws),
                x_prev, *worker_params)
        with jax.named_scope("update"):
            if delay_aware:
                updates, outer_state = outer_opt.update(
                    delta, outer_state, x_prev, delay=delay)
            else:
                updates, outer_state = outer_opt.update(delta, outer_state,
                                                        x_prev)
            x_new = optim.apply_updates(x_prev, updates)
        return x_new, outer_state

    return jax.jit(outer_step)


def merge_params(params_list, weights):
    """Batch-size-weighted parameter average (paper Alg 2, DoMerge).
    Full f32 precision on every platform: a TPU's default matmul
    precision would round the parameters to bf16 on the way."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)
    return jax.tree.map(
        lambda s: jnp.tensordot(w, s.astype(jnp.float32), axes=1,
                                precision=jax.lax.Precision.HIGHEST
                                ).astype(s.dtype),
        stacked)


class StepCache:
    """Compiled inner steps keyed by (micro_batch, accum_steps)."""

    def __init__(self, loss_fn: Callable, inner_opt: optim.Optimizer):
        self.loss_fn = loss_fn
        self.inner_opt = inner_opt
        self._cache: Dict[Tuple[int, int], Callable] = {}

    def get(self, plan: ExecutionPlan):
        key = (plan.micro_batch, plan.accum_steps)
        if key not in self._cache:
            self._cache[key] = make_inner_step(
                self.loss_fn, self.inner_opt, plan.accum_steps)
        return self._cache[key]

    @property
    def num_compiled(self) -> int:
        return len(self._cache)


def reshape_for_plan(batch, plan: ExecutionPlan):
    """Leaves (plan.effective_batch, ...) -> (accum, micro, ...)."""
    return jax.tree.map(
        lambda x: x.reshape(plan.accum_steps, plan.micro_batch, *x.shape[1:]),
        batch)
