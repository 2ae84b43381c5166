"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, laid out as the program's parameter tree.

The program and the reference both start from these values: the program
in the type the configuration serves (bfloat16), the reference from the
same bfloat16 values read back as float32.  Matrices are normal with
standard deviation 1/sqrt(fan_in), the embedding 0.02, and the norm
scales (stored as offsets from 1) 0.02, so that a dropped norm weight
changes the loss.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import Arch

EMBED_STD = 0.02
NORM_STD = 0.02


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


def key_words(seed: int) -> np.ndarray:
    """Two 32-bit words of key data from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        *head, last = path.split("/")
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return tree


def make_weights(a: Arch, seed: int, dtype):
    """The parameter tree in ``dtype``, made on the default device."""
    import jax
    import jax.numpy as jnp

    spec = layout(a)

    def make(words):
        key = jax.random.wrap_key_data(words)
        out = {}
        for i, (path, (shape, std)) in enumerate(sorted(spec.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out[path] = x.astype(dtype)
        return nest(out)

    return jax.jit(make)(jnp.asarray(key_words(seed)))
