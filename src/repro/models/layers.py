"""Shared transformer building blocks (pure JAX).

Design constraints that shape this file:

* **Scan-homogeneous layers** — the pipeline runtime stacks per-layer
  params and scans/shards them, so layer variation (sliding window,
  local/global alternation) is expressed as *per-layer data* (a window
  scalar), never as structural differences.
* **Blockwise attention** — prefill_32k would need O(S²) score
  materialization with naive attention (TBs at full scale); we use an
  online-softmax blockwise formulation so the full-scale dry-runs fit
  HBM: on a TPU the Pallas flash kernels (`kernels/flash_attention.py`),
  elsewhere a lax.scan over KV blocks.  Decode (S_q = 1) uses
  single-shot scores.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import env
from repro.kernels import flash_attention as FA

NEG_INF = -1.0e9


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(p, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"].astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embeddings.  x: (B, S, H, hd); positions: (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = jnp.exp(-math.log(theta) *
                   jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Attention (GQA + sliding window + softcap), blockwise for S_q > 1
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int):
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    so = 1.0 / math.sqrt(num_heads * head_dim)
    return {
        "wq": jax.random.normal(kq, (d_model, num_heads * head_dim)) * s,
        "wk": jax.random.normal(kk, (d_model, num_kv_heads * head_dim)) * s,
        "wv": jax.random.normal(kv, (d_model, num_kv_heads * head_dim)) * s,
        "wo": jax.random.normal(ko, (num_heads * head_dim, d_model)) * so,
    }


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, Hk, hd) -> (B, S, Hk*groups, hd)."""
    if groups == 1:
        return k
    b, s, hk, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hk, groups, hd))
    return k.reshape(b, s, hk * groups, hd)


def blockwise_attention(q, k, v, *, q_pos, k_pos, window, causal=True,
                        attn_softcap=0.0, block_k=512):
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, H, hd) (kv already head-repeated).
    q_pos: (B, Sq) int32; k_pos: (B, Sk) int32.
    window: scalar (may be traced) — key j visible to query i iff
            j <= i (causal) and j > i - window.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    qf = (q * scale).astype(jnp.float32).transpose(0, 2, 1, 3)  # (B,H,Sq,hd)

    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-10**9)
    kb = k.transpose(0, 2, 1, 3).reshape(b, h, nblk, block_k, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(b, h, nblk, block_k, hd)
    kpb = k_pos.reshape(b, nblk, block_k)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp = blk                       # (B,H,bk,hd),(B,H,bk,hd),(B,bk)
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", qf,
                           kblk.astype(jnp.float32))
        s_blk = softcap(s_blk, attn_softcap)
        vis = kp[:, None, None, :] <= q_pos[:, None, :, None] \
            if causal else jnp.ones_like(s_blk, dtype=bool)
        vis &= kp[:, None, None, :] > (q_pos[:, None, :, None] - window)
        s_blk = jnp.where(vis, s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(
        step, init,
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4),
         kpb.transpose(1, 0, 2)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # (B,Sq,H,hd)


# ---------------------------------------------------------------------------
# Flash attention (custom_vjp): O(S) residuals — the blockwise forward
# above saves per-block probabilities under AD (TBs at 32k); this variant
# saves only (o, lse) and re-streams KV blocks in the backward pass.
# ---------------------------------------------------------------------------

def _flash_fwd_scan(qf, kb, vb, kpb, q_pos, *, window, causal, cap):
    """qf: (B,H,Sq,hd) f32 pre-scaled; kb/vb: (nblk,B,H,bk,hd);
    kpb: (nblk,B,bk).  Returns (out f32, m, l)."""
    b, h, sq, hd = qf.shape

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, kp = blk
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", qf,
                           kblk.astype(jnp.float32))
        s_blk = softcap(s_blk, cap)
        vis = kp[:, None, None, :] <= q_pos[:, None, :, None] \
            if causal else jnp.ones_like(s_blk, dtype=bool)
        vis &= kp[:, None, None, :] > (q_pos[:, None, :, None] - window)
        s_blk = jnp.where(vis, s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out, m, l


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, cap: float, block_k: int):
    @jax.custom_vjp
    def flash(q, k, v, q_pos, k_pos, window):
        return _fwd(q, k, v, q_pos, k_pos, window)[0]

    def _prep(q, k, v, k_pos):
        b, sq, h, hd = q.shape
        sk = k.shape[1]
        scale = 1.0 / math.sqrt(hd)
        qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)
        nblk = -(-sk // block_k)
        pad = nblk * block_k - sk
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                            constant_values=-10 ** 9)
        kb = k.transpose(0, 2, 1, 3).reshape(
            b, h, nblk, block_k, hd).transpose(2, 0, 1, 3, 4)
        vb = v.transpose(0, 2, 1, 3).reshape(
            b, h, nblk, block_k, hd).transpose(2, 0, 1, 3, 4)
        kpb = k_pos.reshape(b, nblk, block_k).transpose(1, 0, 2)
        return qf, kb, vb, kpb, pad

    def _fwd(q, k, v, q_pos, k_pos, window):
        qf, kb, vb, kpb, _ = _prep(q, k, v, k_pos)
        out, m, l = _flash_fwd_scan(qf, kb, vb, kpb, q_pos,
                                    window=window, causal=causal, cap=cap)
        o = out.transpose(0, 2, 1, 3).astype(q.dtype)   # (B,Sq,H,hd)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))        # (B,H,Sq)
        return o, (q, k, v, q_pos, k_pos, window, o, lse)

    def _bwd(res, g):
        q, k, v, q_pos, k_pos, window, o, lse = res
        b, sq, h, hd = q.shape
        sk = k.shape[1]
        scale = 1.0 / math.sqrt(hd)
        qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)      # (B,H,Sq,hd)
        gf = g.astype(jnp.float32).transpose(0, 2, 1, 3)
        of = o.astype(jnp.float32).transpose(0, 2, 1, 3)
        delta = jnp.sum(gf * of, axis=-1)                     # (B,H,Sq)
        _, kb, vb, kpb, pad = _prep(q, k, v, k_pos)

        def step(dq, blk):
            kblk, vblk, kp = blk
            kf = kblk.astype(jnp.float32)
            vf = vblk.astype(jnp.float32)
            u = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
            if cap > 0.0:
                s = cap * jnp.tanh(u / cap)
                dsdu = 1.0 - jnp.square(s / cap)
            else:
                s, dsdu = u, 1.0
            vis = kp[:, None, None, :] <= q_pos[:, None, :, None] \
                if causal else jnp.ones_like(s, dtype=bool)
            vis &= kp[:, None, None, :] > (q_pos[:, None, :, None]
                                           - window)
            s = jnp.where(vis, s, NEG_INF)
            p = jnp.exp(s - lse[..., None])                   # (B,H,Sq,bk)
            dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
            ds = p * (dp - delta[..., None]) * dsdu
            ds = jnp.where(vis, ds, 0.0)
            dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
            dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
            return dq, (dk, dv)

        dq0 = jnp.zeros_like(qf)
        dq, (dk_b, dv_b) = jax.lax.scan(step, dq0, (kb, vb, kpb))
        nblk = kb.shape[0]
        dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(b, h, nblk * block_k,
                                                   hd)
        dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(b, h, nblk * block_k,
                                                   hd)
        if pad:
            dk, dv = dk[:, :, :sk], dv[:, :, :sk]
        dq_out = dq.transpose(0, 2, 1, 3).astype(q.dtype)
        dk_out = dk.transpose(0, 2, 1, 3).astype(k.dtype)
        dv_out = dv.transpose(0, 2, 1, 3).astype(v.dtype)
        f0 = jax.dtypes.float0
        return (dq_out, dk_out, dv_out,
                np.zeros(q_pos.shape, f0), np.zeros(k_pos.shape, f0),
                np.zeros(window.shape, f0))

    flash.defvjp(_fwd, _bwd)
    return flash


# (mesh, batch axes) of the GSPMD sections being traced (`rows_over`)
_ROWS: list = []


@contextlib.contextmanager
def rows_over(mesh, axes):
    """Trace attention inside as batch-sharded over ``axes`` of ``mesh``.

    GSPMD cannot partition a Mosaic call, so in a jit over several
    devices the Pallas kernels run under a shard_map that hands each
    device its own rows: attention is per row, nothing crosses devices.
    Axes of the mesh not in ``axes`` see the rows replicated, as GSPMD
    would hold them.  A call already inside a shard_map, and the scan,
    which GSPMD partitions itself, ignore this."""
    _ROWS.append((mesh, axes))
    try:
        yield
    finally:
        _ROWS.pop()


def _kernel_attention(q, k, v, q_pos, k_pos, window, **statics):
    """`FA.flash_attention`, per shard of rows under `rows_over`."""
    fn = functools.partial(FA.flash_attention, **statics)
    if not _ROWS or jax.sharding.get_abstract_mesh().manual_axes:
        return fn(q, k, v, q_pos, k_pos, window)
    mesh, axes = _ROWS[-1]
    rows = P(axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=(rows,) * 5 + (P(),),
                         out_specs=rows, check_vma=False)(
        q, k, v, q_pos, k_pos, window)


def flash_attention(q, k, v, *, q_pos, k_pos, window, causal=True,
                    attn_softcap=0.0, block_k=512, block_q=2048):
    """Memory-lean attention used on all training/prefill paths.
    q: (B, Sq, H, hd); k, v: (B, Sk, Hk, hd) with Hk dividing H.
    window may be a traced per-layer scalar (scan homogeneity).

    On a TPU, shapes the Pallas kernels tile
    (`kernels.flash_attention.block_q_for`) run there, forward and
    backward (per shard of rows inside `rows_over`); elsewhere, and for
    other shapes, the `lax.scan` flash below runs, with the kv heads
    repeated.  Its Q is chunked with lax.map when Sq > block_q: without
    it a 32k prefill materializes (B, H, Sq, block_k) f32 score tiles
    (~13 GB on mixtral).
    """
    w = jnp.asarray(window, jnp.int32)
    sq = q.shape[1]
    if not env.pallas_interpret() and FA.block_q_for(
            sq, block_k, q.shape[-1]) is not None:
        return _kernel_attention(q, k, v, q_pos, k_pos, w, causal=causal,
                                 softcap=attn_softcap, block_k=block_k)
    groups = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, groups), _repeat_kv(v, groups)
    fn = _make_flash(bool(causal), float(attn_softcap), int(block_k))
    if sq <= block_q or sq % block_q:
        return fn(q, k, v, q_pos, k_pos, w)
    nq = sq // block_q

    def chunk(args):
        qc, pc = args
        return fn(qc, k, v, pc, k_pos, w)

    qs = jnp.moveaxis(q.reshape(q.shape[0], nq, block_q, *q.shape[2:]),
                      1, 0)
    ps = jnp.moveaxis(q_pos.reshape(q_pos.shape[0], nq, block_q), 1, 0)
    out = jax.lax.map(chunk, (qs, ps))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def onehot_attention(q, k, v, *, q_pos, k_pos, window, causal=True,
                     attn_softcap=0.0):
    """Single-shot attention for decode (S_q small).

    GQA-aware: k/v may have fewer heads than q (H = Hk * G) — the shared
    kv heads are used in-place, never materialized repeated (a 0.5M-token
    cache repeated 2-4x would dominate decode HBM)."""
    b, sq, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).astype(jnp.float32).reshape(b, sq, hk, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = softcap(s, attn_softcap)
    vis = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None] \
        if causal else jnp.ones_like(s, dtype=bool)
    vis &= k_pos[:, None, None, None, :] > \
        (q_pos[:, None, None, :, None] - window)
    s = jnp.where(vis, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


BIG_WINDOW = 10 ** 9


def attention(p, x, *, num_heads, num_kv_heads, head_dim, rope_theta,
              positions, window, causal=True, attn_softcap=0.0,
              kv_cache=None, cache_index=None, cross_kv=None,
              block_k=512):
    """Full attention sublayer.  x: (B, S, d).

    kv_cache: optional dict {k: (B, Sc, Hk, hd), v: ...} — decode mode:
      new kv written at cache_index, attention runs over the cache.
    cross_kv: optional precomputed (k, v) from an encoder (no causal mask,
      no rope on kv) — whisper cross-attention.
    """
    b, s, _ = x.shape
    dtype = x.dtype
    q = (x @ p["wq"].astype(dtype)).reshape(b, s, num_heads, head_dim)

    if cross_kv is not None:
        k, v = cross_kv
        k_pos = jnp.zeros((b, k.shape[1]), jnp.int32)
        causal = False
        window = BIG_WINDOW
    else:
        k = (x @ p["wk"].astype(dtype)).reshape(b, s, num_kv_heads, head_dim)
        v = (x @ p["wv"].astype(dtype)).reshape(b, s, num_kv_heads, head_dim)
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
        if kv_cache is not None:
            # decode: scatter new kv at cache_index, attend over cache
            k = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), cache_index, 1)
            v = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), cache_index, 1)
            kv_cache = {"k": k, "v": v}
            sc = k.shape[1]
            k_pos = jnp.broadcast_to(jnp.arange(sc, dtype=jnp.int32), (b, sc))
            # positions beyond the write head are invisible (<= q_pos check
            # handles it since they hold garbage but pos > q_pos).
        else:
            k_pos = positions
    if s == 1:
        # decode: GQA handled inside (no repeated cache materialization)
        out = onehot_attention(q, k, v, q_pos=positions, k_pos=k_pos,
                               window=window, causal=causal,
                               attn_softcap=attn_softcap)
    else:
        out = flash_attention(q, k, v, q_pos=positions, k_pos=k_pos,
                              window=window, causal=causal,
                              attn_softcap=attn_softcap, block_k=block_k)
    out = out.reshape(b, s, num_heads * head_dim)
    out = out @ p["wo"].astype(dtype)
    return (out, kv_cache) if kv_cache is not None else (out, None)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU/GeGLU or plain)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, gated: bool = True):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {"w_up": jax.random.normal(k2, (d_model, d_ff)) * s_in,
         "w_down": jax.random.normal(k3, (d_ff, d_model)) * s_out}
    if gated:
        p["w_gate"] = jax.random.normal(k1, (d_model, d_ff)) * s_in
    return p


def mlp(p, x, act: str = "silu"):
    dtype = x.dtype
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    up = x @ p["w_up"].astype(dtype)
    if "w_gate" in p:
        up = fn(x @ p["w_gate"].astype(dtype)) * up
    else:
        up = fn(up)
    return up @ p["w_down"].astype(dtype)
