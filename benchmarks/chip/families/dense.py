"""The dense decoder block: what the benchmark knows of a configuration
whose ``family`` is ``dense`` (``harness.family`` lists the interface).

The block follows the program's dense layer, which departs from the
published models in ways each configuration file lists under ``notes``:
RMSNorm with the scale stored as an offset from 1, the embedding
multiplied by sqrt(d_model), full rotary embedding in the half-split
layout, and no attention bias.

Weights: matrices are normal with standard deviation 1/sqrt(fan_in), the
embedding 0.02, and the norm scales (stored as offsets from 1) 0.02, so
that a dropped norm weight changes the loss.

Model FLOPs per token of a training step are 6 per matmul parameter
(forward, and the two matmuls of the backward pass) plus 12·L·q_dim·S
for the attention scores and their weighted sum over the whole S×S
matrix, as the program computes it.  The embedding lookup is no matmul
and does not count; the output head does.  Recomputation under remat
does not count.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
EMBED_STD = 0.02
NORM_STD = 0.02

#: configuration-file keys that a cut may change, and the program's
#: ``ModelConfig`` field each sets
CUT_KEYS = {"num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
            "hidden_size": "d_model", "intermediate_size": "d_ff",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim"}
#: the CPU tests' sizes (``tests/tiny.py``)
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 512}


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


def arch_of(config: dict) -> Arch:
    heads = config["num_attention_heads"]
    head_dim = (config.get("head_dim") or config["assumed"].get("head_dim")
                or config["hidden_size"] // heads)
    return Arch(d_model=config["hidden_size"],
                num_layers=config["num_hidden_layers"],
                num_heads=heads,
                num_kv_heads=config["num_key_value_heads"],
                head_dim=head_dim, d_ff=config["intermediate_size"],
                vocab_size=config["vocab_size"], qk_norm=config["qk_norm"],
                tied=config["tie_word_embeddings"],
                rope_theta=float(config["rope_theta"]),
                norm_eps=float(config["rms_norm_eps"]))


def program_config(config: dict):
    """The program's ``ModelConfig``: the registry entry with the cut
    keys of ``reduced`` applied.  Raises if any other size disagrees with
    the file, which means the registry no longer holds the published
    model."""
    from repro.configs import get_config

    a = arch_of(config)
    cfg = get_config(config["registry"]).with_overrides(
        **{CUT_KEYS[k]: config[k] if k != "head_dim" else a.head_dim
           for k in config["reduced"]})
    got = Arch(d_model=cfg.d_model, num_layers=cfg.num_layers,
               num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
               head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
               vocab_size=cfg.vocab_size, qk_norm=cfg.qk_norm,
               tied=cfg.tie_embeddings, rope_theta=float(cfg.rope_theta),
               norm_eps=float(cfg.rms_eps))
    if (got != a or cfg.dtype != config["torch_dtype"]
            or cfg.arch_type != "dense" or cfg.moe or cfg.sliding_window):
        raise ValueError(f"the program's {config['registry']} is not the "
                         f"configuration in the file: {got} vs {a}")
    return cfg


def tiny(config: dict) -> dict:
    """The keys that cut ``config`` to the CPU tests' sizes; an MHA
    configuration stays MHA."""
    kv = TINY["num_key_value_heads"]
    if config["num_key_value_heads"] == config["num_attention_heads"]:
        kv = TINY["num_attention_heads"]
    return dict(TINY, num_key_value_heads=kv)


def layout(a: Arch) -> dict:
    """{path: (shape, std)} of every leaf; layer leaves carry a leading
    ``num_layers`` axis."""
    d, L, hd = a.d_model, a.num_layers, a.head_dim
    qd, kvd = a.num_heads * hd, a.num_kv_heads * hd
    leaves = {
        "embed": ((a.vocab_size, d), EMBED_STD),
        "final_norm": ((d,), NORM_STD),
        "layers/attn_norm": ((L, d), NORM_STD),
        "layers/mlp_norm": ((L, d), NORM_STD),
        "layers/attn/q": ((L, d, qd), d ** -0.5),
        "layers/attn/k": ((L, d, kvd), d ** -0.5),
        "layers/attn/v": ((L, d, kvd), d ** -0.5),
        "layers/attn/o": ((L, qd, d), qd ** -0.5),
        "layers/gate": ((L, d, a.d_ff), d ** -0.5),
        "layers/up": ((L, d, a.d_ff), d ** -0.5),
        "layers/down": ((L, a.d_ff, d), a.d_ff ** -0.5),
    }
    if a.qk_norm:
        leaves["layers/attn/q_norm"] = ((L, hd), NORM_STD)
        leaves["layers/attn/k_norm"] = ((L, hd), NORM_STD)
    if not a.tied:
        leaves["lm_head"] = ((d, a.vocab_size), d ** -0.5)
    return leaves


def entry_axes(path: str) -> int:
    """The check compares a layer leaf layer by layer, any other whole."""
    return 1 if path.startswith("layers/") else 0


# ------------------------------------------------------------ reference
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


def loss(params, tokens, a: Arch, quant):
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


# ---------------------------------------------------------------- flops
def matmul_params(a: Arch) -> int:
    d, hd = a.d_model, a.head_dim
    qd, kvd = a.num_heads * hd, a.num_kv_heads * hd
    per_layer = d * qd + 2 * d * kvd + qd * d + 3 * d * a.d_ff
    return a.num_layers * per_layer + d * a.vocab_size


def param_count(a: Arch) -> int:
    d = a.d_model
    norms = a.num_layers * (2 * d + (2 * a.head_dim if a.qk_norm else 0)) + d
    # a tied head is the embedding, already counted among the matmuls
    embed = 0 if a.tied else d * a.vocab_size
    return matmul_params(a) + norms + embed


def train_flops_per_token(a: Arch, seq_len: int) -> float:
    qd = a.num_heads * a.head_dim
    return 6.0 * matmul_params(a) + 12.0 * a.num_layers * qd * seq_len
