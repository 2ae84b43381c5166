"""jit'd public wrapper for the gradstats reduction (padding + interpret
mode on the CPU).  Zero-padding is exact for all four outputs: padded rows
contribute 0 to colsum and produce s=d=0 entries that are sliced off;
the mean ḡ divides by the *true* B.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.gradstats.kernel import LANES, gradstats_padded


def gradstats_reduce(G, *, bb: int = 8, bd: int = 512):
    """G (B, D) -> (s (B,), d (B,), n2 (), b ()).  See core.batching."""
    B, D = G.shape
    bb = min(bb, B)
    bd = min(bd, LANES * -(-D // LANES))     # lane-aligned tile
    pad_b = (-B) % bb
    pad_d = (-D) % bd
    Gp = jnp.pad(G, ((0, pad_b), (0, pad_d))) if (pad_b or pad_d) else G
    interpret = interpret_mode()
    s, d, n2, _ = _stats_fixed_b(Gp, B, bb=bb, bd=bd, interpret=interpret)
    return s[:B], d[:B], n2, jnp.float32(B)


def _stats_fixed_b(Gp, true_b, *, bb, bd, interpret):
    # gradstats_padded divides colsum by padded B; rescale ḡ-dependent
    # outputs to the true row count.
    s, d, n2, _ = gradstats_padded(Gp, bb=bb, bd=bd, interpret=interpret)
    scale = Gp.shape[0] / true_b
    return s, d * scale, n2 * scale * scale, jnp.float32(true_b)
