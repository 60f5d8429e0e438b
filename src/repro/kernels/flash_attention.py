"""Pallas TPU flash-attention (forward) kernel.

The §Roofline baselines show training/prefill are memory-bound on
attention score traffic — the XLA-lowered blockwise attention writes
(block_q × block_k) f32 score/probability tiles to HBM at every step.
This kernel keeps the whole online-softmax state in VMEM scratch:

  grid = (B·H, Sq/block_q, Sk/block_k)   (TPU grid iterates sequentially
                                          over the last axis, so scratch
                                          carries across k-blocks)
  q tile   (block_q, hd)   VMEM           k/v tiles (block_k, hd) VMEM
  scratch  m, l (block_q,) + acc (block_q, hd) f32

HBM traffic drops to q+k+v+o (the flash bound).  GQA is handled in the
index_map (k/v blocks are fetched from the shared kv head — no
materialized head repetition).  Supports causal masking, sliding window,
and gemma-style logit softcap.  Backward remains the JAX-level flash
custom_vjp (models/layers.py); a dedicated bwd kernel is future work.

Validated in interpret mode against ref.flash_attention_ref; like the
codec kernels it compiles through Mosaic on a TPU and runs in interpret
mode elsewhere (`repro.env.pallas_interpret`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import env

NEG_INF = -1.0e9


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, nk: int,
            causal: bool, window: int, softcap: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    vis = k_pos <= q_pos if causal else jnp.full(
        (block_q, block_k), True)
    vis &= k_pos > q_pos - window
    s = jnp.where(vis, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                              "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: int = 10 ** 9, softcap: float = 0.0,
                        block_q: int = 256, block_k: int = 256,
                        interpret: Optional[bool] = None):
    """q: (B, H, Sq, hd); k, v: (B, Hk, Sk, hd) with H % Hk == 0.
    Returns o: (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    hk, sk = k.shape[1], k.shape[2]
    assert h % hk == 0 and sq % block_q == 0 and sk % block_k == 0, (
        q.shape, k.shape, block_q, block_k)
    groups = h // hk
    nq, nk = sq // block_q, sk // block_k
    qf = q.reshape(b * h, sq, hd)
    kf = k.reshape(b * hk, sk, hd)
    vf = v.reshape(b * hk, sk, hd)

    def kv_index(bh, qi, ki):
        # GQA: query head bh -> shared kv head (no repetition in HBM)
        return (bh // groups, ki, 0)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), block_q=block_q,
        block_k=block_k, nk=nk, causal=causal, window=window,
        softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=env.pallas_interpret() if interpret is None
        else interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, hd)
