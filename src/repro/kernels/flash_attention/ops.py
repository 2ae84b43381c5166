"""jit'd public wrapper: padding, window normalization, interpret mode
on the CPU.  Forward-only (serving / prefill); the training path uses the
XLA reference — Pallas kernels have no implicit VJP.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_padded
from repro.models.layers import GLOBAL_WINDOW


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 128, bk: int = 128):
    """q (B,S,H,hd), k/v (B,S,Hk,hd) -> (B,S,H,hd).

    ``window``: None (full), python int, or traced int32 scalar (dynamic
    per-layer windows under lax.scan).
    """
    B, S, H, hd = q.shape
    bq = min(bq, max(8, S))
    bk = min(bk, max(8, S))
    pad = (-S) % max(bq, bk)
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    if window is None:
        w = jnp.full((1,), GLOBAL_WINDOW, jnp.int32)
    else:
        w = jnp.asarray(window, jnp.int32).reshape(1)
    interpret = interpret_mode()
    out = flash_attention_padded(qp, kp, vp, w, causal=causal, bq=bq, bk=bk,
                                 interpret=interpret)
    return out[:, :S] if pad else out
