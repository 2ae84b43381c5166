"""Blocked online-softmax (flash) attention Pallas kernel — TPU target.

Design (TPU-native, not a CUDA port):
  * q/k/v are laid out (batch, head, seq, hd) for the call, with batch
    and head squeezed out of each block, so a block is a (rows, hd)
    tile on the (sublane, lane) axes.
  * grid = (batch, q_head, Sq/BQ, Sk/BK); the last axis is sequential
    ("arbitrary" dimension semantics) and carries the online-softmax
    state (m, l, acc) in VMEM scratch.
  * BQ = BK = 128 aligns the s = q·kᵀ and p·v contractions with the
    128×128 MXU tile; head_dim rides the lane dimension.
  * GQA is handled in the k/v index_map (kv head = q head // group) —
    no materialized head repeat in HBM.
  * causal + sliding-window masking from block-local iotas; the window
    is a *dynamic* scalar (scalar-prefetch) so one compiled kernel
    serves gemma3's interleaved local/global layers under lax.scan.
      VMEM working set per step: BQ·hd (q) + 2·BK·hd (k,v) + BQ·BK (s)
    + BQ·hd (acc) floats ≈ 0.4 MB at hd=128 — comfortably inside 16 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(window_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bq: int, bk: int, scale: float,
                  causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                  # (BQ, hd)
    k = k_ref[...].astype(jnp.float32)                  # (BK, hd)
    v = v_ref[...].astype(jnp.float32)                  # (BK, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    window = window_ref[0]
    mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (BQ, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_padded(q, k, v, window, *, causal: bool = True,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = True):
    """q: (B,S,H,hd), k/v: (B,S,Hk,hd), S divisible by bq/bk.
    window: int32 (1,) — keys with kpos <= qpos - window are masked
    (use a huge value for full attention)."""
    B, S, H, hd = q.shape
    Hk = k.shape[2]
    G = H // Hk
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)

    grid = (B, H, nq, nk)
    sq = pl.squeezed            # kernel sees (rows, hd) tiles
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal)
    # heads move ahead of the sequence so each block's last two dims are
    # (rows, hd): Mosaic tiles only the two minor dims, and a squeezed
    # head dim there is refused
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((sq, sq, bq, hd),
                             lambda b, h, iq, ik, w: (b, h, iq, 0)),
                pl.BlockSpec((sq, sq, bk, hd),
                             lambda b, h, iq, ik, w: (b, h // G, ik, 0)),
                pl.BlockSpec((sq, sq, bk, hd),
                             lambda b, h, iq, ik, w: (b, h // G, ik, 0)),
            ],
            out_specs=pl.BlockSpec((sq, sq, bq, hd),
                                   lambda b, h, iq, ik, w: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, hd), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(window, qt, kt, vt)
    return out.swapaxes(1, 2)
