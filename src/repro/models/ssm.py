"""Mamba2 (SSD — state-space duality) block, pure JAX.

Chunked matmul formulation for train/prefill (arXiv:2405.21060 §6):
within-chunk terms are attention-like matmuls (MXU-friendly), the
inter-chunk recurrence is a lax.scan over chunk states.  Decode uses the
O(1) recurrent state update.

Shapes (g = ssm_groups = 1 throughout):
  x_in   (B, L, d_model)
  z, xh  (B, L, d_inner),  d_inner = expand * d_model
  Bc, Cc (B, L, n)         n = ssm_state
  dt     (B, L, h)         h = d_inner // headdim
  state  (B, h, p, n)      p = headdim
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import rmsnorm


def init_mamba2(key, cfg):
    d, di = cfg.d_model, cfg.d_inner
    n, h, w = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
    g = cfg.ssm_groups
    conv_dim = di + 2 * g * n
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "in_proj": jax.random.normal(
            ks[0], (d, 2 * di + 2 * g * n + h)) * s,
        "conv_w": jax.random.normal(ks[1], (conv_dim, w)) * (1.0 / w),
        "conv_b": jnp.zeros((conv_dim,)),
        "dt_bias": jnp.log(jnp.exp(
            jnp.linspace(1e-3, 1e-1, h)) - 1.0),          # softplus^-1
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "D": jnp.ones((h,)),
        "norm": {"scale": jnp.zeros((di,))},
        "out_proj": jax.random.normal(ks[2], (di, d)) * (1.0 / math.sqrt(di)),
    }


def _split_proj(cfg, proj):
    di, n, h, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_groups
    z, xBC, dt = jnp.split(proj, [di, 2 * di + 2 * g * n], axis=-1)
    return z, xBC, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv.  xBC: (B, L, C); w: (C, width)."""
    width = w.shape[-1]
    pads = jnp.pad(xBC, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    for i in range(width):
        out = out + pads[:, i:i + xBC.shape[1], :].astype(jnp.float32) \
            * w[:, i].astype(jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(xBC.dtype)


def _segsum(x):
    """x: (..., q) -> (..., q, q) with out[i, j] = sum_{j < m <= i} x[m]."""
    xc = jnp.cumsum(x, axis=-1)
    diff = xc[..., :, None] - xc[..., None, :]
    q = x.shape[-1]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(xh, dt, A, Bc, Cc, chunk: int, initial_state=None):
    """Chunked SSD scan.

    xh (B,L,h,p) dt (B,L,h) A (h,) Bc,Cc (B,L,n).
    Returns y (B,L,h,p) and final state (B,h,p,n).
    """
    b, l, h, p = xh.shape
    n = Bc.shape[-1]
    pad = (-l) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))       # dt=0 -> no-op
        Bc = jnp.pad(Bc, ((0, 0), (0, pad), (0, 0)))
        Cc = jnp.pad(Cc, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    c = lp // chunk
    f32 = jnp.float32
    xs = (xh.astype(f32) * dt.astype(f32)[..., None]).reshape(
        b, c, chunk, h, p)                                  # input-scaled
    xr = xh.astype(f32).reshape(b, c, chunk, h, p)
    dA = (dt.astype(f32) * A.astype(f32)).reshape(b, c, chunk, h)
    Bc = Bc.astype(f32).reshape(b, c, chunk, n)
    Cc = Cc.astype(f32).reshape(b, c, chunk, n)

    dA_cs = jnp.cumsum(dA, axis=2)                          # (b,c,q,h)
    # --- intra-chunk (diagonal blocks) ---
    Lmat = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))       # (b,c,h,q,q)
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)              # (b,c,q,q)
    M = Lmat * CB[:, :, None, :, :]
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", M, xs)
    # --- chunk states ---
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)     # (b,c,q,h)
    states = jnp.einsum("bcin,bcih,bcihp->bchpn", Bc, decay_states, xs)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])               # (b,c,h)

    def step(carry, inp):
        st_c, dec_c = inp
        new = carry * dec_c[:, :, None, None] + st_c
        return new, carry                                    # emit incoming

    if initial_state is None:
        init = jnp.zeros((b, h, p, n), f32)
    else:
        init = initial_state.astype(f32)
    final_state, state_in = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4),
                     chunk_decay.transpose(1, 0, 2)))
    state_in = state_in.transpose(1, 0, 2, 3, 4)             # (b,c,h,p,n)
    # --- inter-chunk contribution ---
    y_off = jnp.einsum("bcin,bchpn,bcih->bcihp", Cc, state_in,
                       jnp.exp(dA_cs))
    y = (y_diag + y_off).reshape(b, lp, h, p)[:, :l]
    return y.astype(xh.dtype), final_state


def mamba2_forward(p, x, cfg, initial_state=None):
    """Full Mamba2 mixer.  x: (B, L, d_model) -> (out, state_dict).

    state_dict carries the recurrent handoff for decode: the final SSD
    state and the raw (pre-conv) tail window feeding the causal conv.
    """
    b, l, _ = x.shape
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hd = cfg.ssm_headdim
    dtype = x.dtype
    proj = x @ p["in_proj"].astype(dtype)
    z, xBC_raw, dt_raw = _split_proj(cfg, proj)
    conv_tail = xBC_raw[:, -(cfg.ssm_conv_width - 1):, :]
    xBC = _causal_conv(xBC_raw, p["conv_w"], p["conv_b"])
    xh, Bc, Cc = jnp.split(xBC, [di, di + n], axis=-1)
    xh = xh.reshape(b, l, h, hd)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope(tracing.SSD):
        y, final_state = ssd_chunked(xh, dt, A, Bc, Cc, cfg.ssm_chunk,
                                     initial_state)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, l, di).astype(dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    state = {"ssm": final_state, "conv": conv_tail}
    return y @ p["out_proj"].astype(dtype), state


def mamba2_decode_step(p, x, cfg, ssm_state, conv_state):
    """Single-token recurrent update.

    x: (B, 1, d_model); ssm_state (B,h,p,n); conv_state (B,width-1,conv_dim).
    """
    b = x.shape[0]
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    hd = cfg.ssm_headdim
    dtype = x.dtype
    proj = (x[:, 0] @ p["in_proj"].astype(dtype))
    z, xBC, dt_raw = _split_proj(cfg, proj)
    # conv over the stored window
    window = jnp.concatenate(
        [conv_state, xBC[:, None, :].astype(conv_state.dtype)], axis=1)
    conv_out = jnp.sum(window.astype(jnp.float32)
                       * p["conv_w"].astype(jnp.float32).T[None], axis=1)
    xBC = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32)
                      ).astype(dtype)
    new_conv_state = window[:, 1:]
    xh, Bc, Cc = jnp.split(xBC, [di, di + n], axis=-1)
    xh = xh.reshape(b, h, hd).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))   # (B,h)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A)                                       # (B,h)
    Bf, Cf = Bc.astype(jnp.float32), Cc.astype(jnp.float32)
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt, xh, Bf)
    new_state = ssm_state.astype(jnp.float32) * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cf)
    y = y + xh * p["D"].astype(jnp.float32)[:, None]
    y = y.reshape(b, di).astype(dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = (y @ p["out_proj"].astype(dtype))[:, None, :]
    return out, new_state.astype(ssm_state.dtype), new_conv_state
