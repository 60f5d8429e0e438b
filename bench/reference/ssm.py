"""Plain reference of the ``ssm`` family: Mamba-2 (arXiv:2405.21060;
hf:state-spaces/mamba2-1.3b), the SSD mixer as the whole layer and no
MLP, tied embeddings, in float32 layer by layer.

Each layer, with h the residual stream:

    u = RMSNorm(h)
    [z, xBC, dt] = u · W_in
    xBC = SiLU(causal depthwise conv(xBC) + b)
    x (heads × headdim), B (state), C (state) = split(xBC)
    Δ = softplus(dt + dt_bias),  A = −exp(A_log)
    y_i = Σ_{j≤i} (C_i · B_j) · exp(Σ_{j<m≤i} Δ_m A) · Δ_j x_j + D x_i
    h ← h + RMSNorm(y ⊙ SiLU(z)) · W_out

The SSD is this quadratic form over the whole sequence, not the chunked
state-passing algorithm the program runs: each block of
``QUERY_BLOCK`` query rows against every earlier key, so that one
block's (batch, heads, rows, keys) tiles fit.  Its decay exponents are
sums over the tokens between key and query taken from the block's own
start: backwards to an earlier key, forwards from a key in the block.
No exponent is the difference of two long cumulative sums, which over
a sequence reach |Δ·A| · 1024 ≈ 10⁴ and would lose about 10⁻³ of a
near-diagonal decay in float32.  Matmuls run at the caller's default
matmul precision.

Departures from the published block, which the program makes and this
reference follows (the configuration file lists them under
``assumed``): RMSNorm gains held as ``1 + scale`` (published: a weight
initialised to one); ``A_log`` and ``dt_bias`` initialised on a
linspace (A from 1 to 16, Δ from 1e-3 to 1e-1 over the heads) in place
of the published random draws; the conv weights N(0, 1/width²) in place
of PyTorch's default; float32 throughout in place of bfloat16 with a
float32 residual.  Only one group (``ssm_groups`` 1), as published.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import cross_entropy, mm, normal, rmsnorm

QUERY_BLOCK = 256


def _widths(c: dict) -> tuple:
    if c["ssm_groups"] != 1:
        raise ValueError(f"ssm_groups {c['ssm_groups']}: the reference "
                         f"holds one group, as the published model")
    di = c["ssm_expand"] * c["d_model"]
    return di, c["ssm_state"], di // c["ssm_headdim"], c["ssm_headdim"]


def init_params(c: dict, key) -> dict:
    """Weights from ``key`` in the program's layout: layer-stacked
    arrays under ``layers``, norm gains at zero (gain 1), matrices
    N(0, 1/fan_in), the tied embedding N(0, 0.02²)."""
    d, L, v = c["d_model"], c["num_layers"], c["vocab_size"]
    di, n, nh, _ = _widths(c)
    w = c["ssm_conv_width"]
    conv = di + 2 * n

    def per_layer(x):
        return jnp.broadcast_to(x, (L, *x.shape)).astype(jnp.float32)
    dt0 = jnp.linspace(1e-3, 1e-1, nh)
    return {
        "embed": normal(key, 0, (v, d), 0.02),
        "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
        "layers": {
            "norm1": {"scale": jnp.zeros((L, d), jnp.float32)},
            "mamba": {
                "in_proj": normal(key, 1, (L, d, 2 * di + 2 * n + nh),
                                  1 / math.sqrt(d)),
                "conv_w": normal(key, 2, (L, conv, w), 1 / w),
                "conv_b": jnp.zeros((L, conv), jnp.float32),
                "dt_bias": per_layer(jnp.log(jnp.expm1(dt0))),
                "A_log": per_layer(jnp.log(jnp.linspace(1.0, 16.0, nh))),
                "D": jnp.ones((L, nh), jnp.float32),
                "norm": {"scale": jnp.zeros((L, di), jnp.float32)},
                "out_proj": normal(key, 3, (L, di, d), 1 / math.sqrt(di)),
            },
        },
    }


def _conv(x, w, b):
    """SiLU of the causal depthwise conv of x (B, S, C) with w (C, W):
    out_t = Σ_k w[:, k] · x_{t-W+1+k} + b."""
    width, s = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + s] * w[:, k] for k in range(width))
    return jax.nn.silu(out + b)


def ssd(x, dt, A, B, C, block: int = QUERY_BLOCK):
    """y_i = Σ_{j≤i} (C_i · B_j) exp(Σ_{j<m≤i} Δ_m A) Δ_j x_j, the
    quadratic form, in blocks of ``block`` query rows.  x (b, s, h, p),
    dt (b, s, h), A (h,), B and C (b, s, n); y (b, s, h, p)."""
    s = x.shape[1]
    dA = jnp.moveaxis(dt * A, 1, 2)                          # (b, h, s)
    xs = x * dt[..., None]
    ys = []
    for s0 in range(0, s, block):
        s1 = min(s0 + block, s)
        q = dA[..., s0:s1]                                   # (b, h, Q)
        nq = s1 - s0
        # keys in the block: Σ_{j<m≤i} summed forwards from key j
        i = jnp.arange(nq)[:, None]
        j = jnp.arange(nq)[None, :]
        terms = jnp.where(i > j, q[..., :, None], 0.0)       # [i, j] = q_i
        e = jnp.where(i >= j, jnp.cumsum(terms, axis=-2), -jnp.inf)
        if s0:
            # earlier keys: Σ_{s0≤m≤i} + Σ_{j<m<s0}, the second summed
            # backwards from the block's start
            ahead = jnp.cumsum(q, axis=-1)                   # (b, h, Q)
            back = jnp.cumsum(dA[..., 1:s0][..., ::-1], axis=-1)[..., ::-1]
            back = jnp.concatenate([back, jnp.zeros((*q.shape[:-1], 1))],
                                   axis=-1)                  # (b, h, s0)
            e = jnp.concatenate([ahead[..., :, None] + back[..., None, :],
                                 e], axis=-1)                # (b, h, Q, s1)
        cb = jnp.einsum("bin,bjn->bij", C[:, s0:s1], B[:, :s1])
        m = jnp.exp(e) * cb[:, None]
        ys.append(jnp.einsum("bhij,bjhp->bihp", m, xs[:, :s1]))
    return jnp.concatenate(ys, axis=1)


def _layer(c: dict, lp: dict, h):
    b, s, _ = h.shape
    di, n, nh, p = _widths(c)
    m = lp["mamba"]
    u = rmsnorm(h, lp["norm1"]["scale"], c["norm_eps"])
    z, xbc, dt = jnp.split(mm(u, m["in_proj"]), [di, 2 * di + 2 * n],
                           axis=-1)
    x, B, C = jnp.split(_conv(xbc, m["conv_w"], m["conv_b"]),
                        [di, di + n], axis=-1)
    x = x.reshape(b, s, nh, p)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    y = ssd(x, dt, A, B, C, QUERY_BLOCK) + x * m["D"][:, None]
    y = rmsnorm(y.reshape(b, s, di) * jax.nn.silu(z), m["norm"]["scale"],
                c["norm_eps"])
    return h + mm(y, m["out_proj"])


def loss(c: dict, params: dict, batch: dict, stages: int):
    """(mean cross-entropy, [hidden state after each of the first
    ``stages - 1`` stages]) — the latter are the activations the
    pipeline's boundaries carry."""
    h = params["embed"][batch["tokens"]]
    per = c["num_layers"] // stages
    layer = jax.checkpoint(lambda h, lp: _layer(c, lp, h))
    h, hs = jax.lax.scan(lambda h, lp: (layer(h, lp),) * 2, h,
                         params["layers"])
    bounds = [hs[k * per - 1] for k in range(1, stages)]
    h = rmsnorm(h, params["final_norm"]["scale"], c["norm_eps"])
    logits = mm(h, params["embed"].T)
    return cross_entropy(logits, batch["targets"], batch["mask"]), bounds
