"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, laid out as the program's parameter tree by the
configuration's family (``layout``).

The program and the reference both start from these values: the program
in the type the configuration serves (bfloat16), the reference from the
same values read back as float32.
"""
from __future__ import annotations

import numpy as np


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


def make_weights(layout: dict, seed: int, dtype):
    """The parameter tree of ``layout`` ({path: (shape, std[, dtype])}),
    each leaf normal with its standard deviation, in its own dtype where
    it states one and else in ``dtype``, made on the default device."""
    import jax
    import jax.numpy as jnp

    def make(words):
        key = jax.random.wrap_key_data(words)
        out = {}
        for i, (path, (shape, std, *own)) in enumerate(
                sorted(layout.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out[path] = x.astype(own[0] if own else dtype)
        return nest(out)

    return jax.jit(make)(jnp.asarray(key_words(seed)))
