"""jit'd public wrapper for the Mamba selective-scan kernel: padding to
block multiples + interpret mode on the CPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.mamba_scan.kernel import ROWS, mamba_scan_padded


def mamba_scan(u, dt, A_log, Bm, Cm, *, chunk: int = 128, bd: int = 128):
    """u, dt (B,S,di); A_log (di,n); Bm, Cm (B,S,n) ->
    (y (B,S,di), h_last (B,di,n))."""
    B, S, di = u.shape
    chunk = ROWS * -(-min(chunk, S) // ROWS)   # whole row groups
    bd = min(bd, di)
    pad_s = (-S) % chunk
    pad_d = (-di) % bd
    neg_A = -jnp.exp(A_log.astype(jnp.float32))
    if pad_s or pad_d:
        pd = ((0, 0), (0, pad_s), (0, pad_d))
        u = jnp.pad(u, pd)
        dt = jnp.pad(dt, pd)
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_s), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_s), (0, 0)))
        neg_A = jnp.pad(neg_A, ((0, pad_d), (0, 0)))
    interpret = interpret_mode()
    y, h_last = mamba_scan_padded(u, dt, neg_A, Bm, Cm, chunk=chunk, bd=bd,
                                  interpret=interpret)
    if pad_s or pad_d:
        y = y[:, :S, :di]
        h_last = h_last[:, :di]
    return y, h_last
