"""Selective-scan (Mamba-1) Pallas kernel — TPU target.

Recurrence:  h_t = exp(dt_t ⊙ A) · h_{t-1} + (dt_t u_t) ⊗ B_t
             y_t = <h_t, C_t>  (contraction over the state dim n)

TPU-native layout (vs. the CUDA warp-parallel original):
  * grid = (batch, d_inner/BD, S/CHUNK); the chunk axis is sequential and
    carries the state h in VMEM scratch — the HBM→VMEM pipeline streams
    u/dt/B/C chunk-by-chunk while the recurrence stays resident.
  * h is held as (n, BD): the state dim n=16 rides the sublanes and
    d_inner the lanes, so u_t and dt_t are (1, BD) rows that broadcast
    over sublanes; A arrives transposed, (n, di).  B_t and C_t are (1, n)
    rows turned into (n, 1) columns by a masked lane reduction against
    the identity (no transpose), so they broadcast over lanes.
  * the time loop walks the chunk in groups of ROWS steps: each group is
    one tile-aligned (ROWS, ·) load of u/dt/B/C and one aligned store of
    y, with the ROWS steps inside unrolled at static offsets (Mosaic
    cannot prove a dynamic single-row index tile-aligned).  Each step is
    a handful of (n, BD) multiply-adds on the VPU — the op is
    memory-bound, so VMEM residency (not MXU use) is the roofline lever.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 16      # one (16, 128) bf16 tile; two f32 tiles


def _scan_kernel(u_ref, dt_ref, nA_ref, b_ref, c_ref, y_ref, hout_ref,
                 h_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    nA = nA_ref[...].astype(jnp.float32)                # (n, BD) = -exp(A)ᵀ
    n, bd = nA.shape
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, bd), 0)

    def column(r):                                      # (1, n) -> (n, 1)
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    def group(g, h):
        rows = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        u = u_ref[rows, :].astype(jnp.float32)          # (ROWS, BD)
        dt = dt_ref[rows, :].astype(jnp.float32)
        bm = b_ref[rows, :].astype(jnp.float32)         # (ROWS, n)
        cm = c_ref[rows, :].astype(jnp.float32)
        y = jnp.zeros((ROWS, bd), jnp.float32)
        for t in range(ROWS):
            dt_t = dt[t:t + 1]                          # (1, BD)
            h = (jnp.exp(dt_t * nA) * h
                 + column(bm[t:t + 1]) * (dt_t * u[t:t + 1]))
            y_t = jnp.sum(h * column(cm[t:t + 1]), axis=0, keepdims=True)
            y = jnp.where(row == t, y_t, y)
        y_ref[rows, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // ROWS, group, h_ref[...])
    h_ref[...] = h

    @pl.when(ic == nc - 1)
    def _final():
        hout_ref[...] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "bd", "interpret"))
def mamba_scan_padded(u, dt, neg_A, Bm, Cm, *, chunk: int = 128,
                      bd: int = 128, interpret: bool = True):
    """u, dt: (B,S,di); neg_A: (di,n) = -exp(A_log); Bm, Cm: (B,S,n).
    S % chunk == 0, chunk % ROWS == 0, di % bd == 0.
    Returns (y (B,S,di), h_last (B,di,n))."""
    B, S, di = u.shape
    n = neg_A.shape[1]
    grid = (B, di // bd, S // chunk)
    sq = pl.squeezed
    kernel = functools.partial(_scan_kernel, chunk=chunk)
    y, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((sq, chunk, bd), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((sq, chunk, bd), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((n, bd), lambda b, j, ic: (0, j)),
            pl.BlockSpec((sq, chunk, n), lambda b, j, ic: (b, ic, 0)),
            pl.BlockSpec((sq, chunk, n), lambda b, j, ic: (b, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, chunk, bd), lambda b, j, ic: (b, ic, j)),
            pl.BlockSpec((sq, n, bd), lambda b, j, ic: (b, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((B, n, di), u.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), jnp.float32)],
        interpret=interpret,
    )(u, dt, neg_A.T, Bm, Cm)
    return y, h_last.swapaxes(1, 2)
