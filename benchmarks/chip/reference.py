"""Plain reference of what the timed path computes: the dense decoder
language model's loss and gradients, AdamW, the microbatch batch
statistics, and the Nesterov outer step.

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

The block follows the program's dense layer, which departs from the
published models in ways each configuration file lists under ``notes``:
RMSNorm with the scale stored as an offset from 1, the embedding
multiplied by sqrt(d_model), full rotary embedding in the half-split
layout, and no attention bias.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


class Arch(NamedTuple):
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qk_norm: bool
    tied: bool
    rope_theta: float
    norm_eps: float


def identity(x):
    return x


def round_trip(dtype) -> Callable:
    """Quantizer that stores a value in ``dtype`` and reads it back."""
    def q(x):
        return x.astype(dtype).astype(F32)
    return q


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, positions, theta):
    """x (B, S, H, hd); rotates the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(p, x, a: Arch, quant):
    B, S, _ = x.shape
    mm = lambda u, w: quant(u) @ quant(w)                 # noqa: E731
    pos = jnp.arange(S)
    h = _rms_norm(x, quant(p["attn_norm"]), a.norm_eps)
    q = mm(h, p["attn"]["q"]).reshape(B, S, a.num_heads, a.head_dim)
    k = mm(h, p["attn"]["k"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    v = mm(h, p["attn"]["v"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    if a.qk_norm:
        q = _rms_norm(q, quant(p["attn"]["q_norm"]), a.norm_eps)
        k = _rms_norm(k, quant(p["attn"]["k_norm"]), a.norm_eps)
    q, k = _rope(q, pos, a.rope_theta), _rope(k, pos, a.rope_theta)
    rep = a.num_heads // a.num_kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", quant(q), quant(k))
    scores = scores / math.sqrt(a.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", quant(probs), quant(v))
    x = quant(x + mm(attn.reshape(B, S, -1), p["attn"]["o"]))
    h = _rms_norm(x, quant(p["mlp_norm"]), a.norm_eps)
    g = mm(h, p["gate"])
    up = mm(h, p["up"])
    return quant(x + mm(jax.nn.silu(g) * up, p["down"]))


def loss(params, tokens, a: Arch, quant=identity):
    """Mean next-token cross-entropy of ``tokens`` (B, S)."""
    x = quant(quant(params["embed"])[tokens] * math.sqrt(a.d_model))
    for i in range(a.num_layers):
        x = _layer(jax.tree.map(lambda l: l[i], params["layers"]), x, a,
                   quant)
    x = _rms_norm(x, quant(params["final_norm"]), a.norm_eps)
    head = params["embed"].T if a.tied else params["lm_head"]
    logits = quant(quant(x) @ quant(head))[:, :-1]
    tgt = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def make_grad(a: Arch, quant=identity):
    """jitted (params, tokens (B, S)) -> (loss, grads), float32 at the
    highest matmul precision."""
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
