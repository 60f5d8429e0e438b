"""Plain reference of the ``dense`` family as the program runs it: a
GPT-2-shaped decoder (pre-norm RMSNorm, rotary positions, causal
multi-head attention, a plain GELU MLP, tied embeddings), in float32
layer by layer.

Departures from hf:gpt2-xl that the program makes, and this reference
follows (the configuration file lists them under ``assumed``): RMSNorm
with a ``1 + scale`` gain and no biases in place of LayerNorm with
biases, rotary positions (theta 10,000, half split) in place of learned
absolute positions, and no biases in the projections.  GELU is the tanh
form GPT-2 uses.

Attention forms its probabilities as the configuration states
(``attn_block_k``): over blocks of keys with a running maximum (the
online softmax), each block's exponentials left unnormalised into the
probabilities-times-values matmul and the sum divided out at the end.
At the TPU's default precision that matmul rounds its inputs to
bfloat16, so where the probabilities are normalised decides what is
rounded.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import cross_entropy, mm, normal, rmsnorm


def init_params(c: dict, key) -> dict:
    """Weights from ``key`` in the program's layout: layer-stacked
    arrays under ``layers``, norm gains at zero (gain 1), matrices
    N(0, 1/fan_in), the tied embedding N(0, 0.02²)."""
    d, L, v = c["d_model"], c["num_layers"], c["vocab_size"]
    hq = c["num_heads"] * c["head_dim"]
    hk = c["num_kv_heads"] * c["head_dim"]
    f = c["d_ff"]
    return {
        "embed": normal(key, 0, (v, d), 0.02),
        "final_norm": {"scale": jnp.zeros((d,), jnp.float32)},
        "layers": {
            "norm1": {"scale": jnp.zeros((L, d), jnp.float32)},
            "attn": {"wq": normal(key, 1, (L, d, hq), 1 / math.sqrt(d)),
                     "wk": normal(key, 2, (L, d, hk), 1 / math.sqrt(d)),
                     "wv": normal(key, 3, (L, d, hk), 1 / math.sqrt(d)),
                     "wo": normal(key, 4, (L, hq, d), 1 / math.sqrt(hq))},
            "norm2": {"scale": jnp.zeros((L, d), jnp.float32)},
            "ffn": {"w_up": normal(key, 5, (L, d, f), 1 / math.sqrt(d)),
                    "w_down": normal(key, 6, (L, f, d), 1 / math.sqrt(f))},
        },
    }


def _rope(x, theta: float):
    """x (B, S, H, hd): rotate the two halves of each head by
    position × theta^(-i/half)."""
    half = x.shape[-1] // 2
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = pos[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


MASKED = -1.0e9


def _attend(q, k, v, block: int):
    """Causal softmax attention of q, k, v (B, S, H, hd), the keys
    taken in blocks of ``block`` with a running maximum m, sum l and
    accumulator acc: per block p = exp(s - m_new), acc = acc · exp(m -
    m_new) + p @ v; the output is acc / l."""
    s, hd = q.shape[1], q.shape[-1]
    q = q * (1.0 / math.sqrt(hd))
    qpos = jnp.arange(s)[:, None]
    m = jnp.full(q.shape[:3], MASKED, jnp.float32)           # (B, S, H)
    l = jnp.zeros(q.shape[:3], jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    for j in range(0, s, block):
        kb, vb = k[:, j:j + block], v[:, j:j + block]
        sc = jnp.einsum("bqhd,bkhd->bqhk", q, kb)
        kpos = jnp.arange(j, j + kb.shape[1])[None, :]
        sc = jnp.where((kpos <= qpos)[None, :, None, :], sc, MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, vb)
        m = m_new
    return acc / l[..., None]


def _layer(c: dict, lp: dict, h):
    b, s, _ = h.shape
    nh, nk, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    x = rmsnorm(h, lp["norm1"]["scale"], c["norm_eps"])
    a = lp["attn"]
    q = _rope(mm(x, a["wq"]).reshape(b, s, nh, hd), c["rope_theta"])
    k = _rope(mm(x, a["wk"]).reshape(b, s, nk, hd), c["rope_theta"])
    v = mm(x, a["wv"]).reshape(b, s, nk, hd)
    k = jnp.repeat(k, nh // nk, axis=2)
    v = jnp.repeat(v, nh // nk, axis=2)
    o = _attend(q, k, v, c["attn_block_k"]).reshape(b, s, nh * hd)
    h = h + mm(o, a["wo"])
    x = rmsnorm(h, lp["norm2"]["scale"], c["norm_eps"])
    f = lp["ffn"]
    return h + mm(_gelu(mm(x, f["w_up"])), f["w_down"])


def loss(c: dict, params: dict, batch: dict, stages: int):
    """(mean cross-entropy, [hidden state after each of the first
    ``stages - 1`` stages]) — the latter are the activations the
    pipeline's boundaries carry."""
    h = params["embed"][batch["tokens"]]
    L = c["num_layers"]
    per = L // stages
    layer = jax.checkpoint(lambda lp, h: _layer(c, lp, h))
    bounds = []
    for i in range(L):
        lp = jax.tree.map(lambda x: x[i], params["layers"])
        h = layer(lp, h)
        if (i + 1) % per == 0 and (i + 1) < L:
            bounds.append(h)
    h = rmsnorm(h, params["final_norm"]["scale"], c["norm_eps"])
    logits = mm(h, params["embed"].T)
    return cross_entropy(logits, batch["targets"], batch["mask"]), bounds
