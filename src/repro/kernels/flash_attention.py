"""Pallas TPU flash attention: a forward kernel and a backward pair.

On a TPU the model's attention core (`models.layers.flash_attention`,
every S > 1 attention call) runs these three kernels; elsewhere it runs
the `lax.scan` flash, which is also the reference they are tested
against.  Each kernel keeps its (block_k × block_q) score, probability
and ds tiles in VMEM, so HBM sees only q, k, v, o, dO and the per-query
statistics (the flash bound), and a tile that the mask hides entirely
is skipped with `pl.when`:

  flash_fwd      grid (B, H, Sq/bq, Sk/bk): the online softmax over key
                 blocks; writes o and the per-query log-sum-exp lse
  flash_bwd_dkv  grid (B, Hk, Sk/bk, G·Sq/bq): dK and dV of one key
                 block, summed over the G query heads that share its kv
                 head and over the query blocks
  flash_bwd_dq   grid (B, H, Sq/bq, Sk/bk): dQ summed over key blocks

Layout: each head is held transposed, (hd, S), with the sequence on the
128 lanes.  That is the layout the model's projections and RoPE
produce, so no transpose or lane padding of a 64-wide head dim is paid
in HBM; tiles are (keys, queries), so m, l, lse and delta are lane rows
and key positions the only column.

The backward is the scan's flash backward: delta = Σ dO·O per query,
p = exp(s − lse), ds = p·(dp − delta) with dp = dO·Vᵀ, times the
softcap's derivative.  Masks come from the positions, as in the scan:
key j is visible to query i iff k_pos[j] <= q_pos[i] (causal) and
k_pos[j] > q_pos[i] − window, the window a traced scalar (a per-layer
value inside the layer scan) handed in by scalar prefetch, with each
block's least and greatest position.  From those a tile is dead (no
visible key: skipped), full (every key visible: the mask is not
computed, it would change nothing) or partial.  Skipping changes no
number: in the scan such a block gives p = 0 and a correction of 1, or
is wiped by a correction of exactly 0 once a visible block comes.  GQA
reads the shared kv head through the index_map; nothing is repeated in
HBM.

Arithmetic is the scan's at the default matmul precision: each MXU
operand is rounded where the platform's default-precision dot rounds
it — to bfloat16 on a TPU (one MXU pass: q·scale and k in s, the
unnormalised p and v in p@v, and in the backward p/dO, dO/v, ds/k,
ds/q), not at all in interpret mode, where the host's dot is float32 —
and accumulates in float32; m, l, lse, delta, exp and ds stay float32.
The key block is the caller's `block_k`, so the online softmax rescales
where the scan does.  Unlike the scan, the kernels do not follow
`jax.default_matmul_precision`: under "highest" on a TPU the scan's
einsums run in float32 while the kernels still round to bfloat16.

Like the codec kernels these compile through Mosaic on a TPU and run in
interpret mode elsewhere (`repro.env.pallas_interpret`).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import env

NEG_INF = -1.0e9
PAD_POS = -10 ** 9          # position of a padding key: never visible
BLOCK_Q = (512, 256, 128)   # query blocks tried, largest first
LANES = 128
TN = (((0,), (0,)), ((), ()))   # a.T @ b
NT = (((1,), (1,)), ((), ()))   # a @ b.T
NN = (((1,), (0,)), ((), ()))   # a @ b


def block_q_for(sq: int, block_k: int, hd: int) -> Optional[int]:
    """The query block the kernels tile ``sq`` queries with, or None when
    the shapes are not theirs to tile (the caller runs the scan): query
    and key blocks are lane dims of a tile, so multiples of 128, and the
    head dim fills whole MXU passes."""
    if block_k % LANES or hd % 64:
        return None
    return next((b for b in BLOCK_Q if sq % b == 0), None)


def _mxu(interpret: bool):
    """The dtype MXU operands are rounded to: what the platform's
    default-precision dot does (bf16 compiled, none interpreted)."""
    return jnp.float32 if interpret else jnp.bfloat16


def _dot(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                               preferred_element_type=jnp.float32)


def _col(row):
    """(1, n) lane row -> (n, 1) column, by a 2-D transpose Mosaic
    lowers (the row broadcast to 128 sublanes first)."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _capped(u, softcap: float):
    """Softcapped scores and their derivative by the raw scores."""
    if softcap <= 0.0:
        return u, 1.0
    s = softcap * jnp.tanh(u / softcap)
    return s, 1.0 - jnp.square(s / softcap)


def _bounds(q_pos, k_pos, window, block_q: int, block_k: int):
    """Scalar-prefetch operands: the window and each block's least and
    greatest position, flattened batch-major."""
    b = q_pos.shape[0]
    qb = q_pos.reshape(b, -1, block_q)
    kb = k_pos.reshape(b, -1, block_k)
    return (jnp.reshape(window, (1,)).astype(jnp.int32),
            qb.min(-1).reshape(-1), qb.max(-1).reshape(-1),
            kb.min(-1).reshape(-1), kb.max(-1).reshape(-1))


def _tile(bounds, b, qi, ki, nq, nk, causal: bool):
    """(live, full) of tile (qi, ki) of batch row b: whether it may hold
    a visible key, and whether every key of it is visible to every
    query."""
    win, qlo, qhi, klo, khi = bounds
    ql, qh = qlo[b * nq + qi], qhi[b * nq + qi]
    kl, kh = klo[b * nk + ki], khi[b * nk + ki]
    live, full = kh > ql - win[0], kl > qh - win[0]
    if causal:
        live = jnp.logical_and(live, kl <= qh)
        full = jnp.logical_and(full, kh <= ql)
    return live, full


def _scores(kt, qt, qp_ref, kp_ref, win, *, scale, causal, softcap,
            masked, mxu):
    """(keys, queries) tile of the (softcapped, masked) scores of key
    block kt and query block qt, both (hd, n), and dscores/draw."""
    s = _dot(kt, qt, TN, mxu)
    if scale != 1.0:
        s = s * scale
    s, dsdu = _capped(s, softcap)
    vis = None
    if masked:
        kp, qp = _col(kp_ref[0]), qp_ref[0]
        vis = kp > qp - win[0]
        if causal:
            vis = jnp.logical_and(vis, kp <= qp)
        s = jnp.where(vis, s, NEG_INF)
    return s, dsdu, vis


def _when_tile(tile, step):
    """Run ``step(masked)`` on a live tile: unmasked where every key is
    visible, masked otherwise."""
    live, full = tile
    pl.when(full)(functools.partial(step, False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(
        functools.partial(step, True))


def _params():
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(win, qlo, qhi, klo, khi, q_ref, k_ref, v_ref, qp_ref,
                kp_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, softcap, mxu):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        # q·scale is rounded, as the scan scales q before its einsum
        qt = q_ref[0, 0].astype(jnp.float32) * scale
        s, _, _ = _scores(k_ref[0, 0], qt, qp_ref, kp_ref, win, scale=1.0,
                          causal=causal, softcap=softcap, masked=masked,
                          mxu=mxu)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _dot(v_ref[0, 0], p, NN, mxu)
        m_scr[...] = m_new

    _when_tile(_tile((win, qlo, qhi, klo, khi), b, qi, ki, nq, nk, causal),
               step)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def forward(q, k, v, q_pos, k_pos, window, *, causal: bool, softcap: float,
            block_q: int, block_k: int, interpret: Optional[bool] = None):
    """flash_fwd.  q: (B, H, hd, Sq); k, v: (B, Hk, hd, Sk), H % Hk == 0;
    q_pos (B, Sq), k_pos (B, Sk) int32; window an int32 scalar.
    Returns o (B, H, hd, Sq) in q's dtype and lse (B, H, 1, Sq) f32."""
    b, h, hd, sq = q.shape
    hk, sk = k.shape[1], k.shape[3]
    groups = h // hk
    interpret = env.pallas_interpret() if interpret is None else interpret
    kernel = functools.partial(
        _fwd_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        softcap=softcap, mxu=_mxu(interpret))
    q_spec = pl.BlockSpec((1, 1, hd, block_q),
                          lambda bi, hi, qi, ki, *_: (bi, hi, 0, qi))
    kv_spec = pl.BlockSpec((1, 1, hd, block_k),
                           lambda bi, hi, qi, ki, *_: (bi, hi // groups,
                                                       0, ki))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, h, sq // block_q, sk // block_k),
            in_specs=[
                q_spec, kv_spec, kv_spec,
                pl.BlockSpec((1, 1, block_q),
                             lambda bi, hi, qi, ki, *_: (bi, 0, qi)),
                pl.BlockSpec((1, 1, block_k),
                             lambda bi, hi, qi, ki, *_: (bi, 0, ki)),
            ],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda bi, hi, qi, ki, *_: (bi, hi, 0, qi)),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((1, block_q), jnp.float32),
                pltpu.VMEM((hd, block_q), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        compiler_params=_params(),
        name="flash_fwd",
        interpret=interpret,
    )(*_bounds(q_pos, k_pos, window, block_q, block_k), q, k, v,
      q_pos[:, None, :], k_pos[:, None, :])


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qp_ref, kp_ref,
              win, *, scale, causal, softcap, masked, mxu):
    """p and ds of one (keys, queries) tile, both f32."""
    s, dsdu, vis = _scores(k_ref[0, 0], q_ref[0, 0], qp_ref, kp_ref, win,
                           scale=scale, causal=causal, softcap=softcap,
                           masked=masked, mxu=mxu)
    p = jnp.exp(s - lse_ref[0, 0])
    dp = _dot(v_ref[0, 0], do_ref[0, 0], TN, mxu)
    ds = p * (dp - di_ref[0, 0]) * dsdu
    if masked:
        ds = jnp.where(vis, ds, 0.0)
    return p, ds


def _dkv_kernel(win, qlo, qhi, klo, khi, q_ref, k_ref, v_ref, do_ref,
                lse_ref, di_ref, qp_ref, kp_ref, dk_ref, dv_ref, dk_scr,
                dv_scr, *, nq, mxu, **statics):
    b, ki, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          qp_ref, kp_ref, win, masked=masked, mxu=mxu,
                          **statics)
        dv_scr[...] += _dot(do_ref[0, 0], p, NT, mxu)
        dk_scr[...] += _dot(q_ref[0, 0], ds, NT, mxu) * statics["scale"]

    _when_tile(_tile((win, qlo, qhi, klo, khi), b, j % nq, ki, nq,
                     pl.num_programs(2), statics["causal"]), step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(win, qlo, qhi, klo, khi, q_ref, k_ref, v_ref, do_ref,
               lse_ref, di_ref, qp_ref, kp_ref, dq_ref, dq_scr, *, mxu,
               **statics):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          qp_ref, kp_ref, win, masked=masked, mxu=mxu,
                          **statics)
        dq_scr[...] += _dot(k_ref[0, 0], ds, NN, mxu) * statics["scale"]

    _when_tile(_tile((win, qlo, qhi, klo, khi), b, qi, ki, nq, nk,
                     statics["causal"]), step)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def backward(q, k, v, q_pos, k_pos, window, o, lse, do, *, causal: bool,
             softcap: float, block_q: int, block_k: int,
             interpret: Optional[bool] = None):
    """flash_bwd_dkv and flash_bwd_dq: (dq, dk, dv) in the dtypes and
    layouts of (q, k, v), from forward's o and lse and the output
    cotangent do (all as `forward` lays them out)."""
    b, h, hd, sq = q.shape
    hk, sk = k.shape[1], k.shape[3]
    groups, nq = h // hk, sq // block_q
    interpret = env.pallas_interpret() if interpret is None else interpret
    statics = dict(scale=1.0 / math.sqrt(hd), causal=causal,
                   softcap=softcap, mxu=_mxu(interpret))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=2, keepdims=True)                 # (B, H, 1, Sq)
    operands = (*_bounds(q_pos, k_pos, window, block_q, block_k),
                q, k, v, do, lse, delta, q_pos[:, None, :],
                k_pos[:, None, :])

    # dK, dV: grid (b, kv head, key block, group head × query block)
    def qh(bi, hi, ki, j, *_):
        return (bi, hi * groups + j // nq, 0, j % nq)

    kv_spec = pl.BlockSpec((1, 1, hd, block_k),
                           lambda bi, hi, ki, j, *_: (bi, hi, 0, ki))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, hk, sk // block_k, groups * nq),
            in_specs=[
                pl.BlockSpec((1, 1, hd, block_q), qh), kv_spec, kv_spec,
                pl.BlockSpec((1, 1, hd, block_q), qh),
                pl.BlockSpec((1, 1, 1, block_q), qh),
                pl.BlockSpec((1, 1, 1, block_q), qh),
                pl.BlockSpec((1, 1, block_q),
                             lambda bi, hi, ki, j, *_: (bi, 0, j % nq)),
                pl.BlockSpec((1, 1, block_k),
                             lambda bi, hi, ki, j, *_: (bi, 0, ki)),
            ],
            out_specs=[kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((hd, block_k), jnp.float32)] * 2),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(*operands)

    # dQ: grid (b, head, query block, key block)
    def qm(bi, hi, qi, ki, *_):
        return (bi, hi, 0, qi)

    kv_spec = pl.BlockSpec((1, 1, hd, block_k),
                           lambda bi, hi, qi, ki, *_: (bi, hi // groups,
                                                       0, ki))
    q_spec = pl.BlockSpec((1, 1, hd, block_q), qm)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **statics),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, h, nq, sk // block_k),
            in_specs=[
                q_spec, kv_spec, kv_spec, q_spec,
                pl.BlockSpec((1, 1, 1, block_q), qm),
                pl.BlockSpec((1, 1, 1, block_q), qm),
                pl.BlockSpec((1, 1, block_q),
                             lambda bi, hi, qi, ki, *_: (bi, 0, qi)),
                pl.BlockSpec((1, 1, block_k),
                             lambda bi, hi, qi, ki, *_: (bi, 0, ki)),
            ],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((hd, block_q), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(),
        name="flash_bwd_dq",
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make(causal: bool, softcap: float, block_q: int, block_k: int):
    statics = dict(causal=causal, softcap=softcap, block_q=block_q,
                   block_k=block_k)

    @jax.custom_vjp
    def attend(q, k, v, q_pos, k_pos, window):
        return forward(q, k, v, q_pos, k_pos, window, **statics)[0]

    def _fwd(q, k, v, q_pos, k_pos, window):
        o, lse = forward(q, k, v, q_pos, k_pos, window, **statics)
        return o, (q, k, v, q_pos, k_pos, window, o, lse)

    def _bwd(res, do):
        q, k, v, q_pos, k_pos, window, o, lse = res
        dq, dk, dv = backward(q, k, v, q_pos, k_pos, window, o, lse, do,
                              **statics)
        f0 = jax.dtypes.float0
        return (dq, dk, dv, np.zeros(q_pos.shape, f0),
                np.zeros(k_pos.shape, f0), np.zeros(window.shape, f0))

    attend.defvjp(_fwd, _bwd)
    return attend


def flash_attention(q, k, v, q_pos, k_pos, window, *, causal: bool = True,
                    softcap: float = 0.0, block_k: int = 512,
                    block_q: Optional[int] = None):
    """Differentiable attention through the three kernels, in the
    model's layout.

    q: (B, Sq, H, hd); k, v: (B, Sk, Hk, hd) with H % Hk == 0;
    q_pos (B, Sq), k_pos (B, Sk) int32; window an int32 scalar, may be
    traced.  Keys are padded to a multiple of ``block_k`` (position
    `PAD_POS`, never visible), as the scan pads them.  ``block_q``
    defaults to `block_q_for`'s choice.  Returns (B, Sq, H, hd)."""
    sk = k.shape[1]
    if block_q is None:
        block_q = block_q_for(q.shape[1], block_k, q.shape[3])
    pad = -sk % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)),
                        constant_values=PAD_POS)
    fn = _make(bool(causal), float(softcap), int(block_q), int(block_k))
    heads_first = lambda x: x.transpose(0, 2, 3, 1)      # (B, H, hd, S)
    o = fn(heads_first(q), heads_first(k), heads_first(v),
           q_pos.astype(jnp.int32), k_pos.astype(jnp.int32),
           jnp.asarray(window, jnp.int32))
    return o.transpose(0, 3, 1, 2)
