"""Plain reference of what the timed path computes: the language model's
loss and gradients (its family's ``loss``, ``families/<family>.py``),
AdamW, the microbatch batch statistics, and the Nesterov outer step.

Written from the architecture's equations in straightforward
``jax.numpy``, in float32 at the highest matmul precision.  It imports
nothing of the program and is given nothing the program made: the
weights come from ``weights.make_weights`` with the run's seed, the
tokens from the benchmark's own feed.

``quant`` is applied to every stored parameter, every matmul input and
the residual stream.  The reference proper uses the identity; the
precision control passes a round trip through a narrower float
(``float8_e4m3fn`` for a configuration served in bfloat16), which is how
the control computes "in the nearest precision below" the stated one.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def identity(x):
    return x


def round_trip(dtype) -> Callable:
    """Quantizer that stores a value in ``dtype`` and reads it back."""
    def q(x):
        return x.astype(dtype).astype(F32)
    return q


def make_grad(loss: Callable, a, quant=identity):
    """jitted (params, tokens (B, S)) -> (loss, grads) of a family's
    ``loss``, float32 at the highest matmul precision."""
    def f(params, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(params, tokens, a, quant)
    return jax.jit(f)


class AdamW(NamedTuple):
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float

    def init(self, params):
        return {"m": jax.tree.map(jnp.zeros_like, params),
                "v": jax.tree.map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def step(self, params, state, grads, quant=identity):
        """One update; jitted by the caller."""
        t = state["t"] + 1
        m = jax.tree.map(lambda m, g: self.b1 * m + (1 - self.b1) * g,
                         state["m"], grads)
        v = jax.tree.map(lambda v, g: self.b2 * v + (1 - self.b2) * g * g,
                         state["v"], grads)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t

        def upd(p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
            return quant(p - self.lr * (u + self.weight_decay * p))

        return (jax.tree.map(upd, params, m, v),
                {"m": m, "v": v, "t": t})


def microbatch_stats(grads_list, micro: int):
    """Norm-test statistics from J workers' mean gradients, each over
    ``micro`` samples: ||mean||^2 and sigma^2 = micro * the rows' sample
    variance (trace of the covariance), summed leaf by leaf."""
    J = len(grads_list)
    n2 = ss = 0.0
    for leaves in zip(*(jax.tree.leaves(g) for g in grads_list)):
        mean = sum(leaves) / J
        n2 = n2 + jnp.sum(mean * mean)
        ss = ss + sum(jnp.sum(jnp.square(l - mean)) for l in leaves)
    return {"mean_norm2": n2, "sigma2": ss / max(J - 1, 1) * micro}


def nesterov_outer(x0, worker_sum, *, workers: int, lr: float,
                   momentum: float, quant=identity):
    """One outer Nesterov step from zero momentum on
    delta = x0 - worker_sum / workers."""
    def one(x, s):
        delta = x - s / workers
        return quant(x - lr * (momentum * delta + delta))
    return jax.tree.map(one, x0, worker_sum)
