"""Fused gradient-moment reduction Pallas kernel — TPU target.

This is the hot loop of the paper's adaptive batching: every outer step,
the norm / inner-product tests need per-sample statistics over the
(B, D) matrix of flattened per-sample gradients (D = model dim, huge;
B = probe batch).  The naive jnp formulation reads G three times
(mean, row-norms, G@ḡ).  The kernel computes

    colsum_j = Σ_i G_ij          (pass 1 — for ḡ)
    s_i = Σ_j G_ij²,  d_i = Σ_j G_ij · ḡ_j     (pass 2, fused)

so G streams HBM→VMEM exactly twice (once per pass) instead of three
times, with f32 accumulators in VMEM.

Layout: every block is 2-D with a lane dim that is a multiple of 128, as
Mosaic requires.  Pass 1: grid = (D/BD, B/BB), row axis sequential,
colsum is a (1, D) row.  Pass 2: grid = (B/BB, D/BD), D axis sequential;
s and d accumulate as (B, 128) per-lane partial sums (each (BB, BD) tile
folds onto 128 lanes with aligned static slices), summed over the lanes
outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _colsum_kernel(g_ref, out_ref):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)                  # (BB, BD)
    out_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def _fold_lanes(x, bd: int):
    """(BB, BD) -> (BB, 128): sum of the BD/128 lane-aligned slices."""
    acc = x[:, :LANES]
    for c in range(1, bd // LANES):
        acc = acc + x[:, c * LANES:(c + 1) * LANES]
    return acc


def _moments_kernel(g_ref, gbar_ref, s_ref, d_ref, *, bd: int):
    jd = pl.program_id(1)

    @pl.when(jd == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        d_ref[...] = jnp.zeros_like(d_ref)

    g = g_ref[...].astype(jnp.float32)                  # (BB, BD)
    gbar = gbar_ref[...]                                # (1, BD) f32
    s_ref[...] += _fold_lanes(g * g, bd)
    d_ref[...] += _fold_lanes(g * gbar, bd)


@functools.partial(jax.jit, static_argnames=("bb", "bd", "interpret"))
def gradstats_padded(G, *, bb: int = 8, bd: int = 512,
                     interpret: bool = True):
    """G: (B, D) with B % bb == 0, D % bd == 0, bd % 128 == 0.
    Returns (s (B,), d (B,), n2 (), b ())."""
    B, D = G.shape
    colsum = pl.pallas_call(
        _colsum_kernel,
        grid=(D // bd, B // bb),
        in_specs=[pl.BlockSpec((bb, bd), lambda jd, ib: (ib, jd))],
        out_specs=pl.BlockSpec((1, bd), lambda jd, ib: (0, jd)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
    )(G)
    gbar = colsum / B
    s, d = pl.pallas_call(
        functools.partial(_moments_kernel, bd=bd),
        grid=(B // bb, D // bd),
        in_specs=[
            pl.BlockSpec((bb, bd), lambda ib, jd: (ib, jd)),
            pl.BlockSpec((1, bd), lambda ib, jd: (0, jd)),
        ],
        out_specs=[
            pl.BlockSpec((bb, LANES), lambda ib, jd: (ib, 0)),
            pl.BlockSpec((bb, LANES), lambda ib, jd: (ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(G, gbar)
    n2 = jnp.sum(jnp.square(gbar))
    return jnp.sum(s, axis=1), jnp.sum(d, axis=1), n2, jnp.float32(B)
