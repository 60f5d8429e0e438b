"""Unified model builder: config -> init / train-forward / prefill / decode.

One code path per *family* (dense-like, ssm, hybrid, audio), all built from
the shared sublayers.  Trunks are `lax.scan`s over layer-stacked params so
full-scale HLOs stay small (critical: this container compiles on one CPU
core) and so the pipeline runtime can shard the same stacked arrays over
the `model` mesh axis.

Layer heterogeneity (gemma2 local/global windows) is *data* — a per-layer
window vector — so every scanned layer is structurally identical.
DeepSeek-style leading dense layers live outside the scan ("prefix").
Zamba2 is scanned as uniform super-blocks of (shared_attn_every mamba
layers + the shared attention block).

The trunk accepts an optional ``boundary_fn`` invoked between pipeline
stage groups — this is where AQ-SGD / DirectQ compression plugs in for the
bit-faithful simulated trainer (training/simulated.py).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

Params = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn_layer(cfg: ModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": L.init_rmsnorm(cfg.d_model),
         "attn": L.init_attention(k1, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim),
         "norm2": L.init_rmsnorm(cfg.d_model)}
    return p, (k2, k3)


def _init_dense_layer(cfg: ModelConfig, key):
    p, (k2, _) = _init_attn_layer(cfg, key)
    p["ffn"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated)
    return p


def _init_moe_layer(cfg: ModelConfig, key):
    p, (k2, _) = _init_attn_layer(cfg, key)
    p["ffn"] = M.init_moe(k2, cfg.d_model, cfg.n_experts, cfg.moe_d_ff,
                          cfg.n_shared_experts, gated=cfg.mlp_gated)
    return p


def _init_mamba_layer(cfg: ModelConfig, key):
    return {"norm1": L.init_rmsnorm(cfg.d_model),
            "mamba": S.init_mamba2(key, cfg)}


def _init_enc_layer(cfg: ModelConfig, key):
    return _init_dense_layer(cfg, key)


def _init_dec_layer(cfg: ModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    p = _init_dense_layer(cfg, k1)
    p["norm_x"] = L.init_rmsnorm(cfg.d_model)
    p["xattn"] = L.init_attention(k2, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.head_dim)
    return p


def _stack_init(init_one: Callable, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_one)(keys)


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    p: dict = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model))
        * 0.02,
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.vocab_size)) / math.sqrt(cfg.d_model)

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        n_scan = cfg.num_layers - cfg.first_dense_layers
        if cfg.first_dense_layers:
            p["prefix"] = [
                _init_dense_layer(cfg, jax.random.fold_in(ks[2], i))
                for i in range(cfg.first_dense_layers)]
        init_one = (functools.partial(_init_moe_layer, cfg) if cfg.has_moe
                    else functools.partial(_init_dense_layer, cfg))
        p["layers"] = _stack_init(init_one, ks[3], n_scan)
    elif fam == "ssm":
        p["layers"] = _stack_init(
            functools.partial(_init_mamba_layer, cfg), ks[3], cfg.num_layers)
    elif fam == "hybrid":
        p["layers"] = _stack_init(
            functools.partial(_init_mamba_layer, cfg), ks[3], cfg.num_layers)
        sp = _init_dense_layer(cfg, ks[4])
        p["shared_block"] = sp
    elif fam == "audio":
        p["enc_layers"] = _stack_init(
            functools.partial(_init_enc_layer, cfg), ks[3],
            cfg.encoder_layers)
        p["enc_norm"] = L.init_rmsnorm(cfg.d_model)
        p["layers"] = _stack_init(
            functools.partial(_init_dec_layer, cfg), ks[4], cfg.num_layers)
    else:
        raise ValueError(fam)
    return p


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def window_vector(cfg: ModelConfig, seq_len: int, n: int,
                  offset: int = 0) -> jax.Array:
    return jnp.array([cfg.layer_window(i + offset, seq_len)
                      for i in range(n)], jnp.int32)


def _attn_ffn_layer(cfg: ModelConfig, lp, h, positions, window, *,
                    cache=None, cache_index=None, block_k=512,
                    expert_map=None, moe_per_sequence=False,
                    moe_ep=None):
    """One dense/moe decoder layer.  Returns (h, new_cache, aux)."""
    with jax.named_scope(tracing.ATTN):
        a, new_cache = L.attention(
            lp["attn"], L.rmsnorm(lp["norm1"], h, cfg.norm_eps),
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            positions=positions, window=window,
            attn_softcap=cfg.attn_softcap, kv_cache=cache,
            cache_index=cache_index, block_k=block_k)
    h = h + a
    with jax.named_scope(tracing.FFN):
        hn = L.rmsnorm(lp["norm2"], h, cfg.norm_eps)
        if "router" in lp.get("ffn", {}):
            ep_axis, ep_size, ep_w = moe_ep if moe_ep else (None, 0, None)
            f, aux = M.moe_ffn(lp["ffn"], hn, top_k=cfg.top_k,
                               capacity_factor=cfg.capacity_factor,
                               act=cfg.act, expert_map=expert_map,
                               per_sequence=moe_per_sequence,
                               ep_axis=ep_axis, ep_size=ep_size,
                               ep_weights=ep_w)
        else:
            f, aux = L.mlp(lp["ffn"], hn, act=cfg.act), 0.0
    return h + f, new_cache, aux


def _mamba_layer(cfg: ModelConfig, lp, h):
    out, _ = S.mamba2_forward(
        lp["mamba"], L.rmsnorm(lp["norm1"], h, cfg.norm_eps), cfg)
    return h + out


# ---------------------------------------------------------------------------
# trunk (training / prefill forward), with optional stage boundaries
# ---------------------------------------------------------------------------

def _scan_layers(step, h, stacked, xs_extra=None, remat=False):
    body = jax.checkpoint(step) if remat else step
    xs = (stacked,) if xs_extra is None else (stacked, *xs_extra)
    (h, aux), _ = jax.lax.scan(lambda c, x: (body(c, x), None), (h, 0.0), xs)
    return h, aux


def _stage_slice(layers, sl: slice):
    """One stage's layers of the stacked weights.  The slice of each
    attention or FFN block's weights (its norm's included) runs under
    that block's scope, so the slice, and the cast to the matmul's
    precision that the compiler fuses into it, are timed with it."""
    scopes = {}
    if "attn" in layers:
        scopes.update(norm1=tracing.ATTN, attn=tracing.ATTN)
    if "ffn" in layers:
        scopes.update(norm2=tracing.FFN, ffn=tracing.FFN)

    def take(path, a):
        scope = scopes.get(getattr(path[0], "key", None))
        if scope is None:
            return a[sl]
        with jax.named_scope(scope):
            return a[sl]
    return jax.tree_util.tree_map_with_path(take, layers)


def trunk_forward(params: Params, cfg: ModelConfig, h: jax.Array,
                  positions: jax.Array, *,
                  num_stages: int = 1,
                  boundary_fn: Optional[Callable] = None,
                  boundary_state: Any = None,
                  remat: bool = False,
                  block_k: int = 512):
    """Apply the layer trunk.  h: (B, S, d) post-embedding.

    ``boundary_fn(state, h, idx) -> (state, h)`` runs between stage groups
    (idx = 0 .. num_stages-2).  Returns (h, aux_loss, boundary_state).
    """
    fam = cfg.family
    seq = h.shape[1]
    aux_total = 0.0

    if fam in ("dense", "vlm", "moe", "audio", "ssm"):
        n_scan = cfg.num_layers - cfg.first_dense_layers
        offset = cfg.first_dense_layers
        for i, lp in enumerate(params.get("prefix", [])):
            h, _, aux = _attn_ffn_layer(cfg, lp, h, positions,
                                        cfg.layer_window(i, seq),
                                        block_k=block_k)
            aux_total += aux
        assert n_scan % num_stages == 0, (cfg.name, n_scan, num_stages)
        per_stage = n_scan // num_stages
        windows = window_vector(cfg, seq, n_scan, offset)

        if fam == "audio":
            xk_all, xv_all = params["_enc_out"]   # (L,B,Se,Hk,hd) each

            def step(carry, xs):
                hh, aux = carry
                lp, w, k_l, v_l = xs
                hh, _, a = _attn_ffn_layer(cfg, lp, hh, positions, w,
                                           block_k=block_k)
                xa, _ = L.attention(
                    lp["xattn"],
                    L.rmsnorm(lp["norm_x"], hh, cfg.norm_eps),
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                    positions=positions, window=L.BIG_WINDOW,
                    cross_kv=(k_l, v_l), block_k=block_k)
                return (hh + xa, aux + a)
        elif fam == "ssm":
            def step(carry, xs):
                hh, aux = carry
                lp, _ = xs
                return (_mamba_layer(cfg, lp, hh), aux)
        else:
            def step(carry, xs):
                hh, aux = carry
                lp, w = xs
                hh, _, a = _attn_ffn_layer(cfg, lp, hh, positions, w,
                                           block_k=block_k)
                return (hh, aux + a)

        for s in range(num_stages):
            sl = slice(s * per_stage, (s + 1) * per_stage)
            stacked = _stage_slice(params["layers"], sl)
            if fam == "audio":
                xs_extra = (windows[sl], xk_all[sl], xv_all[sl])
            else:
                xs_extra = (windows[sl],)
            h, aux = _scan_layers(step, h, stacked, xs_extra, remat=remat)
            aux_total += aux
            if boundary_fn is not None and s < num_stages - 1:
                boundary_state, h = boundary_fn(boundary_state, h, s)
        return h, aux_total, boundary_state

    if fam == "hybrid":
        per = cfg.shared_attn_every
        n_blocks = cfg.num_layers // per
        assert n_blocks % num_stages == 0, (cfg.name, n_blocks, num_stages)

        def block_step(carry, xs):
            hh, aux = carry
            (blk_params,) = xs
            def inner(c, lp):
                return (_mamba_layer(cfg, lp, c), None)
            hh, _ = jax.lax.scan(inner, hh, blk_params)
            hh, _, _ = _attn_ffn_layer(cfg, params["shared_block"], hh,
                                       positions, seq, block_k=block_k)
            return (hh, aux)

        blocks = jax.tree.map(
            lambda a: a.reshape(n_blocks, per, *a.shape[1:]),
            params["layers"])
        per_stage = n_blocks // num_stages
        for s in range(num_stages):
            sl = slice(s * per_stage, (s + 1) * per_stage)
            stacked = jax.tree.map(lambda a: a[sl], blocks)
            h, aux = _scan_layers(block_step, h, stacked, remat=remat)
            aux_total += aux
            if boundary_fn is not None and s < num_stages - 1:
                boundary_state, h = boundary_fn(boundary_state, h, s)
        return h, aux_total, boundary_state

    raise ValueError(fam)


def encode_audio(params: Params, cfg: ModelConfig, frames: jax.Array,
                 remat: bool = False, block_k: int = 512):
    """Whisper encoder over stubbed frame embeddings (B, S_enc, d)."""
    h = frames
    pos = jnp.broadcast_to(
        jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2])

    def step(carry, xs):
        hh, aux = carry
        (lp,) = xs
        a, _ = L.attention(lp["attn"],
                           L.rmsnorm(lp["norm1"], hh, cfg.norm_eps),
                           num_heads=cfg.num_heads,
                           num_kv_heads=cfg.num_kv_heads,
                           head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                           positions=pos, window=L.BIG_WINDOW, causal=False,
                           block_k=block_k)
        hh = hh + a
        hh = hh + L.mlp(lp["ffn"], L.rmsnorm(lp["norm2"], hh, cfg.norm_eps),
                        act=cfg.act)
        return (hh, aux)

    h, _ = _scan_layers(step, h, params["enc_layers"], remat=remat)
    return L.rmsnorm(params["enc_norm"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# embedding / head / losses
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, extra_embeds=None):
    """tokens (..., S_text) -> (..., S, d); extra_embeds (patches/frames)
    are prepended along the sequence dim (pixtral stub)."""
    with jax.named_scope(tracing.EMBED):
        h = params["embed"].astype(cfg.jax_dtype)[tokens]
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=-2)
    return h


@jax.named_scope(tracing.LM_HEAD)
def lm_logits(params, cfg: ModelConfig, h):
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = h @ head.astype(h.dtype)
    return L.softcap(logits.astype(jnp.float32), cfg.final_softcap)


@jax.named_scope(tracing.LM_HEAD)
def cross_entropy(logits, targets, mask):
    """logits (B,S,V) fp32; targets (B,S) int; mask (B,S) {0,1}."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(params: Params, cfg: ModelConfig, batch: dict, *,
            num_stages: int = 1, boundary_fn=None, boundary_state=None,
            remat: bool = False, block_k: int = 512):
    """batch: tokens (B,S_t), targets (B,S_t), mask (B,S_t), optional
    patches (B,P,d) [vlm] or frames (B,S_enc,d) [audio]."""
    tokens = batch["tokens"]
    extra = batch.get("patches")
    h = embed_tokens(params, cfg, tokens, extra)
    b, s = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.family == "audio":
        params = dict(params)
        enc = encode_audio(params, cfg, batch["frames"], remat=remat,
                           block_k=block_k)
        # pre-compute per-layer cross kv lazily inside layers from enc
        params["_enc_out"] = _cross_kv_all(params, cfg, enc)
    h, aux, boundary_state = trunk_forward(
        params, cfg, h, positions, num_stages=num_stages,
        boundary_fn=boundary_fn, boundary_state=boundary_state,
        remat=remat, block_k=block_k)
    if extra is not None:                       # drop patch positions
        h = h[:, extra.shape[1]:]
    logits = lm_logits(params, cfg, h)
    ce = cross_entropy(logits, batch["targets"], batch["mask"])
    total = ce + cfg.router_aux_weight * aux
    return total, {"ce": ce, "aux": aux, "boundary_state": boundary_state}


# ---------------------------------------------------------------------------
# serving: caches, prefill, single-token decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch_size: int, cache_len: int,
                dtype=jnp.bfloat16) -> dict:
    """Zero caches for prefill/decode.  Shapes mirror the dry-run specs."""
    b, hk, hd = batch_size, cfg.num_kv_heads, cfg.head_dim
    caches: dict = {"pos": jnp.zeros((), jnp.int32)}
    fam = cfg.family
    if fam in ("dense", "vlm", "moe", "audio"):
        n_scan = cfg.num_layers - cfg.first_dense_layers
        caches["k"] = jnp.zeros((n_scan, b, cache_len, hk, hd), dtype)
        caches["v"] = jnp.zeros((n_scan, b, cache_len, hk, hd), dtype)
        if cfg.first_dense_layers:
            caches["pk"] = jnp.zeros(
                (cfg.first_dense_layers, b, cache_len, hk, hd), dtype)
            caches["pv"] = jnp.zeros_like(caches["pk"])
        if fam == "audio":
            caches["xk"] = jnp.zeros(
                (cfg.num_layers, b, cfg.encoder_seq, hk, hd), dtype)
            caches["xv"] = jnp.zeros_like(caches["xk"])
    if fam in ("ssm", "hybrid"):
        h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        caches["ssm"] = jnp.zeros(
            (cfg.num_layers, b, h, p, n), jnp.float32)
        caches["conv"] = jnp.zeros(
            (cfg.num_layers, b, cfg.ssm_conv_width - 1, conv_dim), dtype)
    if fam == "hybrid":
        n_blocks = cfg.num_layers // cfg.shared_attn_every
        caches["k"] = jnp.zeros((n_blocks, b, cache_len, hk, hd), dtype)
        caches["v"] = jnp.zeros_like(caches["k"])
    return caches


def _trivial_expert_map(name, leaf, e):
    return jax.lax.dynamic_index_in_dim(leaf, e, 0, keepdims=False)


def _attn_layer_cached(cfg, lp, h, positions, window, cache_k, cache_v,
                       cache_index, block_k, xkv=None):
    """Dense/MoE layer with cache read/write; returns h, (k, v), aux."""
    # prefill (S >> 1): per-sequence dispatch keeps sort/scatter local to
    # the batch shard; sequential expert scan bounds (E, cap, ff) temps
    prefill_moe = cfg.has_moe and h.shape[1] > 1
    emap = _trivial_expert_map if prefill_moe else None
    h, new_cache, aux = _attn_ffn_layer(
        cfg, lp, h, positions, window,
        cache={"k": cache_k, "v": cache_v}, cache_index=cache_index,
        block_k=block_k, expert_map=emap, moe_per_sequence=prefill_moe)
    if xkv is not None:                       # audio cross attention
        xa, _ = L.attention(
            lp["xattn"], L.rmsnorm(lp["norm_x"], h, cfg.norm_eps),
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            positions=positions, window=L.BIG_WINDOW,
            cross_kv=xkv, block_k=block_k)
        h = h + xa
    return h, (new_cache["k"], new_cache["v"]), aux


def _staged_cached_scan(step, carry, xs, *, num_stages, boundary_fn,
                        boundary_state, get_h, set_h):
    """`lax.scan` over stacked layers, cut into ``num_stages``
    contiguous chunks with ``boundary_fn(state, h, idx)`` applied to
    the carried hidden state between chunks — the serving mirror of
    `trunk_forward`'s stage loop, for scans that also thread per-layer
    cache slices through ``xs``/``ys``.  Returns
    (carry, ys, boundary_state)."""
    if num_stages == 1 or boundary_fn is None:
        carry, ys = jax.lax.scan(step, carry, xs)
        return carry, ys, boundary_state
    n = jax.tree.leaves(xs)[0].shape[0]
    assert n % num_stages == 0, (n, num_stages)
    per = n // num_stages
    parts = []
    for si in range(num_stages):
        sl = slice(si * per, (si + 1) * per)
        carry, y = jax.lax.scan(step, carry,
                                jax.tree.map(lambda a: a[sl], xs))
        parts.append(y)
        if si < num_stages - 1:
            boundary_state, h = boundary_fn(boundary_state,
                                            get_h(carry), si)
            carry = set_h(carry, h)
    ys = jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *parts)
    return carry, ys, boundary_state


def forward_with_caches(params: Params, cfg: ModelConfig, tokens, caches,
                        *, patches=None, frames=None, block_k: int = 512,
                        logits_last_only: bool = False,
                        num_stages: int = 1, boundary_fn=None,
                        kv_codec=None):
    """Unified prefill (S > 1) / decode (S = 1) step.

    tokens: (B, S).  Returns (logits (B, S, V) fp32, new_caches).
    logits_last_only: return only the final position's logits — essential
    for full-scale prefill (B×S×V logits would be TBs).

    Serving-plane hooks (`repro.serving`):

    * ``num_stages``/``boundary_fn`` — cut the layer scan into pipeline
      stage groups and run ``boundary_fn(state, h, idx) -> (state, h)``
      on the hidden state between them (the compressed decode hop,
      `serving.delta.DeltaHopCodec`).  The hop's reference buffers ride
      IN the cache dict under ``"hop_m"`` (f32 (nb, B, 1, d)) so they
      batch/shard/vmap exactly like the KV state they live next to.
    * ``kv_codec`` — a `serving.kvcache.KVCodec` with ``bits > 0``
      switches the scanned ``k``/``v`` stores to the quantized layout
      (``{k,v}_codes``/``{k,v}_scale``, see `serving.kvcache`):
      dequantize-on-attend, then encode only this step's fresh rows.
    """
    caches = dict(caches)
    pos0 = caches.pop("pos")
    hop_m = caches.pop("hop_m", None)
    boundary_state = {"m": hop_m} if hop_m is not None else None
    quant = (kv_codec is not None and kv_codec.bits
             and cfg.family in ("dense", "vlm", "moe", "audio"))
    h = embed_tokens(params, cfg, tokens, patches)
    b, s = h.shape[0], h.shape[1]
    positions = pos0 + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, s))
    if "k" in caches:
        cache_len = caches["k"].shape[2]
    elif "k_codes" in caches:
        cache_len = caches["k_codes"].shape[2]
    else:
        cache_len = 0
    fam = cfg.family
    aux = 0.0
    new_caches = {"pos": pos0 + s}

    if fam == "audio" and frames is not None:    # (re)compute cross kv
        enc = encode_audio(params, cfg, frames, block_k=block_k)
        xk, xv = _cross_kv_all(params, cfg, enc)
        caches["xk"], caches["xv"] = (xk.astype(caches["xk"].dtype),
                                      xv.astype(caches["xv"].dtype))

    if fam in ("dense", "vlm", "moe", "audio"):
        n_scan = cfg.num_layers - cfg.first_dense_layers
        windows = window_vector(cfg, cache_len, n_scan,
                                cfg.first_dense_layers)
        for i, lp in enumerate(params.get("prefix", [])):
            h, (nk, nv), a = _attn_layer_cached(
                cfg, lp, h, positions, cfg.layer_window(i, cache_len),
                caches["pk"][i], caches["pv"][i], pos0, block_k)
            caches["pk"] = caches["pk"].at[i].set(nk)
            caches["pv"] = caches["pv"].at[i].set(nv)
            aux += a
        if cfg.first_dense_layers:
            new_caches["pk"], new_caches["pv"] = caches["pk"], caches["pv"]

        def step(carry, xs):
            hh, auxc = carry
            if quant:
                if fam == "audio":
                    lp, w, kc, ksc, vc, vsc, xk_l, xv_l = xs
                    xkv = (xk_l, xv_l)
                else:
                    lp, w, kc, ksc, vc, vsc = xs
                    xkv = None
                ck = kv_codec.decode(kc, ksc, cfg.jax_dtype)
                cv = kv_codec.decode(vc, vsc, cfg.jax_dtype)
            else:
                if fam == "audio":
                    lp, w, ck, cv, xk_l, xv_l = xs
                    xkv = (xk_l, xv_l)
                else:
                    lp, w, ck, cv = xs
                    xkv = None
            hh, (nk, nv), a = _attn_layer_cached(
                cfg, lp, hh, positions, w, ck, cv, pos0, block_k, xkv)
            if quant:
                # encode ONLY this step's fresh rows back into the code
                # store — old tokens keep their original single encoding
                fk = jax.lax.dynamic_slice_in_dim(nk, pos0, s, axis=1)
                fv = jax.lax.dynamic_slice_in_dim(nv, pos0, s, axis=1)
                sk = kv_codec.append({"codes": kc, "scale": ksc}, fk, pos0)
                sv = kv_codec.append({"codes": vc, "scale": vsc}, fv, pos0)
                return (hh, auxc + a), (sk["codes"], sk["scale"],
                                        sv["codes"], sv["scale"])
            return (hh, auxc + a), (nk, nv)

        if quant:
            xs = (params["layers"], windows,
                  caches["k_codes"], caches["k_scale"],
                  caches["v_codes"], caches["v_scale"])
        else:
            xs = (params["layers"], windows, caches["k"], caches["v"])
        if fam == "audio":
            xs = xs + (caches["xk"], caches["xv"])
        (h, aux2), ys, boundary_state = _staged_cached_scan(
            step, (h, 0.0), xs, num_stages=num_stages,
            boundary_fn=boundary_fn, boundary_state=boundary_state,
            get_h=lambda c: c[0], set_h=lambda c, hh: (hh, c[1]))
        aux += aux2
        if quant:
            (new_caches["k_codes"], new_caches["k_scale"],
             new_caches["v_codes"], new_caches["v_scale"]) = ys
        else:
            new_caches["k"], new_caches["v"] = ys
        if fam == "audio":
            new_caches["xk"], new_caches["xv"] = caches["xk"], caches["xv"]

    elif fam == "ssm":
        def step(hh, xs):
            lp, st, cv = xs
            hin = L.rmsnorm(lp["norm1"], hh, cfg.norm_eps)
            if s == 1:
                out, nst, ncv = S.mamba2_decode_step(
                    lp["mamba"], hin, cfg, st, cv)
            else:
                out, state = S.mamba2_forward(lp["mamba"], hin, cfg,
                                              initial_state=st)
                nst, ncv = state["ssm"], state["conv"].astype(cv.dtype)
            return hh + out, (nst.astype(st.dtype), ncv)

        h, (nst, ncv), boundary_state = _staged_cached_scan(
            step, h, (params["layers"], caches["ssm"], caches["conv"]),
            num_stages=num_stages, boundary_fn=boundary_fn,
            boundary_state=boundary_state,
            get_h=lambda c: c, set_h=lambda c, hh: hh)
        new_caches["ssm"], new_caches["conv"] = nst, ncv

    elif fam == "hybrid":
        per = cfg.shared_attn_every
        n_blocks = cfg.num_layers // per
        blocks = jax.tree.map(
            lambda a: a.reshape(n_blocks, per, *a.shape[1:]),
            params["layers"])
        sstates = caches["ssm"].reshape(n_blocks, per,
                                        *caches["ssm"].shape[1:])
        cstates = caches["conv"].reshape(n_blocks, per,
                                         *caches["conv"].shape[1:])

        def block_step(hh, xs):
            blk, sst, cst, ck, cv = xs

            def inner(c, ixs):
                lp, st, cvs = ixs
                hin = L.rmsnorm(lp["norm1"], c, cfg.norm_eps)
                if s == 1:
                    out, nst, ncv = S.mamba2_decode_step(
                        lp["mamba"], hin, cfg, st, cvs)
                else:
                    out, state = S.mamba2_forward(lp["mamba"], hin, cfg,
                                                  initial_state=st)
                    nst = state["ssm"]
                    ncv = state["conv"].astype(cvs.dtype)
                return c + out, (nst.astype(st.dtype), ncv)

            hh, (nst, ncv) = jax.lax.scan(inner, hh, (blk, sst, cst))
            hh, (nk, nv), _ = _attn_layer_cached(
                cfg, params["shared_block"], hh, positions,
                cfg.sliding_window or cache_len, ck, cv, pos0, block_k)
            return hh, (nst, ncv, nk, nv)

        h, (nst, ncv, nk, nv), boundary_state = _staged_cached_scan(
            block_step, h,
            (blocks, sstates, cstates, caches["k"], caches["v"]),
            num_stages=num_stages, boundary_fn=boundary_fn,
            boundary_state=boundary_state,
            get_h=lambda c: c, set_h=lambda c, hh: hh)
        new_caches["ssm"] = nst.reshape(caches["ssm"].shape)
        new_caches["conv"] = ncv.reshape(caches["conv"].shape)
        new_caches["k"], new_caches["v"] = nk, nv
    else:
        raise ValueError(fam)

    if hop_m is not None:
        new_caches["hop_m"] = boundary_state["m"]
    if patches is not None:
        h = h[:, patches.shape[1]:]
    if logits_last_only:
        h = h[:, -1:]
    logits = lm_logits(params, cfg, h)
    return logits, new_caches


def _cross_kv_all(params, cfg: ModelConfig, enc_out):
    """The audio decoder consumes the same encoder memory at every layer;
    we pass raw (k=v=enc projections) per layer inside the scan instead of
    stacking L copies — here we just return the encoder output and let the
    layer project it (cheap: S_enc=1500)."""
    # project per layer inside the scan: attention() receives cross_kv as
    # (k, v) *after* head reshape; we defer projection by passing enc_out
    # through a closure — see _attn_ffn cross path.  To keep the scan
    # homogeneous we project here with the *stacked* per-layer weights.
    wk = params["layers"]["xattn"]["wk"]        # (L, d, Hk*hd)
    wv = params["layers"]["xattn"]["wv"]
    b, se, d = enc_out.shape
    k = jnp.einsum("bsd,ldh->lbsh", enc_out, wk.astype(enc_out.dtype))
    v = jnp.einsum("bsd,ldh->lbsh", enc_out, wv.astype(enc_out.dtype))
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    return (k.reshape(cfg.num_layers, b, se, hk, hd),
            v.reshape(cfg.num_layers, b, se, hk, hd))
