"""Pallas TPU kernels for the AQ-SGD boundary hot path.

The per-boundary critical path is: delta = a − m; rowwise absmax scale;
b-bit quantize; dense bit-pack (sender) and unpack; dequantize; buffer
accumulate (receiver).  Unfused, this chain makes ~6 HBM round-trips over
the activation; each kernel below fuses its whole side into ONE pass
(read a,m → write packed, scale, m_new), which is what makes compression
free on the compute critical path (paper §3.3).

Four fused ops cover every boundary crossing in the pipeline:

* ``delta_quantize_pack``      — AQ-SGD sender (delta → wire + m_new);
* ``dequant_unpack_accumulate``— AQ-SGD receiver (wire + m → m_new);
* ``quantize_pack``            — DirectQ sender, backward-gradient
                                 quantize, and z-bit buffer writes;
* ``unpack_dequant``           — the matching receiver / buffer read.

Three further variants carry the data-parallel *gradient* wire
(core.grad_compress / core.collectives — the paper's Fig. 5
"end-to-end communication compression"):

* ``quantize_pack_scaled``     — quantize with a caller-supplied rowwise
                                 scale (the pmax-shared scale of a
                                 compressed allreduce) and pack;
* ``unpack_codes``             — unpack the wire payload to int32 codes
                                 (the code-domain ``psum`` accumulator);
* ``dequant_sum_mean``         — turn the int32 code *sum* over n
                                 workers back into the mean gradient.

Three more carry the *ring* form of that wire
(`core.collectives.ring_ef_reduce_mean_bucket` — packed codes on the
ppermute hops, local accumulation):

* ``quantize_codes_scaled``     — codes-only encode (optionally also
                                  packed): one pass emits the int32
                                  accumulator form and, for the ring,
                                  the packed wire payload — no on-device
                                  pack→unpack round trip;
* ``unpack_accumulate``         — the ring's accumulate step: unpack an
                                  incoming packed segment and add it to
                                  the local int32 code accumulator in
                                  one pass;
* ``pack_sums`` / ``unpack_sums`` — the ring's all-gather payload: code
                                  *sums* packed at the narrowest width
                                  holding n*(2**b - 1)
                                  (`Q.sum_wire_bits`).

Stochastic rounding takes the uniform noise tensor as an explicit kernel
input rather than seeding the on-core PRNG (pltpu.prng_random_bits): the
reference jnp backend consumes the *same* noise, which is what makes the
two backends bit-identical — the contract tests/test_boundary_parity.py
enforces.  On real TPUs the noise input costs one extra HBM read; the
encode kernels therefore also accept an OPT-IN ``seed`` path
(`REPRO_ONCORE_PRNG=1` at the boundary layer) that draws the uniform
noise on-core via ``pltpu.prng_seed``/``prng_random_bits`` instead.
That path relaxes the ref↔pallas contract to a statistical one (gated
by a dedicated 10k-trial unbiasedness test in test_grad_compress.py)
and is TPU-only: interpret mode has no CPU lowering for ``prng_seed``
(`repro.kernels.ops.oncore_prng_supported` probes for it).

TPU mapping: rows (tokens) are tiled along the grid; each grid step holds
a (BLOCK_R, d) tile in VMEM — d (the model dim, ≤ 8 KiB per row in bf16)
stays whole so the rowwise absmax is a single in-VMEM reduction.  Codes
are int32 inside the kernels (Mosaic casts f32 <-> i32, not f32 <-> u32,
and reduces no unsigned integers).  The wire layout is planar
(docs/WIRE_FORMATS.md): byte j of a row holds codes j, j+pw, ...,
j+(k-1)*pw, so packing is k shift-ors of contiguous lane blocks and
unpacking is k shift-masks concatenated along the lanes — no lane split
into (d/k, k) and no (rows, pw, k) intermediate.

Kernels run in interpret mode exactly when the default backend is not
a TPU (`repro.env.pallas_interpret`, resolved at the first call); on a
TPU they compile through Mosaic, and a kernel Mosaic refuses raises.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import env
from repro.core import quantization as Q

_EPS = 1e-12
DEFAULT_BLOCK_R = 128
_GOLDEN = 0x9E3779B1 - (1 << 32)   # 2**32 / golden ratio, as an i32


def _interpret(interpret: Optional[bool]) -> bool:
    """An explicit choice wins; None follows the platform."""
    return env.pallas_interpret() if interpret is None else interpret


def _oncore_uniform(shape, seed_ref):
    """Uniform(0,1) drawn from the on-core PRNG (TPU only).

    Seeds with the two key words, the first xor-ed with the grid
    position times an odd constant (Mosaic takes at most two seed
    words), so every block gets its own stream and block i of one key
    does not replay block i-1 of the next; the top 24 bits of each
    32-bit draw give an exact-in-f32 uniform on {0, ..., 2**24-1} / 2**24."""
    block = pl.program_id(0) * jnp.int32(_GOLDEN)
    pltpu.prng_seed(seed_ref[0] ^ block, seed_ref[1])
    rb = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.int32)
    top = jax.lax.shift_right_logical(rb, jnp.int32(8))
    return top.astype(jnp.float32) * (1.0 / (1 << 24))


def _seed_spec():
    """BlockSpec for the (2,) i32 seed of the on-core PRNG path."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _noise_arg(u, seed, row_spec):
    """Shared plumbing for the encode entry points: at most one of
    (u, seed) may be given.  Returns (extra_args, extra_specs, mode)."""
    assert u is None or seed is None, "pass uniform noise OR a PRNG seed"
    if u is not None:
        return [u], [row_spec], "input"
    if seed is not None:
        return [jnp.asarray(seed, jnp.int32)], [_seed_spec()], "oncore"
    return [], [], "none"


def _kernel_noise(noise, rest, shape):
    """Pop the noise operand (if any) off `rest` and realize the uniform
    tensor for `_quant_codes`; `shape` is the block's value shape."""
    rest = list(rest)
    if noise == "input":
        return rest.pop(0)[...], rest
    if noise == "oncore":
        return _oncore_uniform(shape, rest.pop(0)), rest
    return None, rest


def _levels(bits: int) -> int:
    return (1 << bits) - 1


def _quant_codes(x, scale, bits: int, u=None):
    """f32 values + rowwise scale -> i32 codes on the uniform grid.

    u: uniform(0,1) noise of x.shape for stochastic rounding (the same
    comparison `u < frac` as jax.random.bernoulli, so codes match the
    reference backend bit-for-bit); None = round-to-nearest.
    """
    lv = _levels(bits)
    y = jnp.clip((x / scale + 1.0) * (0.5 * lv), 0.0, lv)
    if u is None:
        return jnp.round(y).astype(jnp.int32)
    lo = jnp.floor(y)
    bump = (u < (y - lo)).astype(jnp.float32)
    return (lo + bump).astype(jnp.int32)


def _join_planes(vals, planes: int, shift: int):
    """(r, planes*w) i32 values < 2**shift -> (r, w) i32: lane block i
    (lanes i*w ... (i+1)*w) lands at bit shift i*shift."""
    w = vals.shape[-1] // planes
    acc = vals[:, :w]
    for i in range(1, planes):
        acc = acc | (vals[:, i * w:(i + 1) * w] << (i * shift))
    return acc


def _split_planes(vals, planes: int, shift: int):
    """Inverse of `_join_planes`: (r, w) i32 -> (r, planes*w) i32."""
    if planes == 1:
        return vals
    mask = (1 << shift) - 1
    return jnp.concatenate([(vals >> (i * shift)) & mask
                            for i in range(planes)], axis=-1)


def _pack(codes, bits: int):
    """(r, d) i32 codes -> (r, d*bits/8) u8, planar, k codes per byte."""
    return _join_planes(codes, 8 // bits, bits).astype(jnp.uint8)


def _unpack(packed, bits: int):
    """(r, pw) u8 -> (r, pw * 8/bits) i32 codes (inverse of `_pack`)."""
    return _split_planes(packed.astype(jnp.int32), 8 // bits, bits)


def _dequant(codes, scale, bits: int):
    # the reference chain's own formula, so both backends round
    # identically (the bit-identical backend contract)
    return Q.dequantize_sum_mean(codes, scale, bits, 1)


# ---------------------------------------------------------------------------
# AQ-SGD sender: delta -> quantize -> pack (+ buffer update)
# ---------------------------------------------------------------------------

def _dqp_kernel(a_ref, m_ref, *rest, bits: int, noise: str):
    u, (packed_ref, scale_ref, mnew_ref) = _kernel_noise(
        noise, rest, a_ref.shape)
    a = a_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    delta = a - m
    scale = jnp.maximum(jnp.max(jnp.abs(delta), axis=-1, keepdims=True),
                        _EPS)
    codes = _quant_codes(delta, scale, bits, u)
    packed_ref[...] = _pack(codes, bits)
    scale_ref[...] = scale
    mnew_ref[...] = (m + _dequant(codes, scale, bits)).astype(mnew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def delta_quantize_pack(a, m, u=None, *, bits: int, seed=None,
                        block_r: int = DEFAULT_BLOCK_R,
                        interpret: Optional[bool] = None):
    """a, m: (R, d); u: optional uniform noise (R, d) for stochastic
    rounding (or seed: (2,) i32 for the on-core PRNG path, TPU only).
    Returns (packed (R, d//(8/bits)) u8, scale (R, 1) f32,
    m_new (R, d) f32)."""
    assert bits in (2, 4, 8), bits
    r, d = a.shape
    k = 8 // bits
    assert d % k == 0, (d, bits)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    nargs, nspecs, noise = _noise_arg(u, seed, row_spec)
    in_specs = [row_spec, row_spec] + nspecs
    args = [a, m] + nargs
    return pl.pallas_call(
        functools.partial(_dqp_kernel, bits=bits, noise=noise),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, d // k), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, d // k), jnp.uint8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, d), jnp.float32),
        ],
        name="delta_quantize_pack",
        interpret=_interpret(interpret),
    )(*args)


# ---------------------------------------------------------------------------
# AQ-SGD receiver: unpack -> dequantize -> accumulate into the buffer
# ---------------------------------------------------------------------------

def _dua_kernel(packed_ref, scale_ref, m_ref, mnew_ref, *, bits: int):
    codes = _unpack(packed_ref[...], bits)
    m = m_ref[...].astype(jnp.float32)
    mnew_ref[...] = (m + _dequant(codes, scale_ref[...], bits)
                     ).astype(mnew_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def dequant_unpack_accumulate(packed, scale, m, *, bits: int,
                              block_r: int = DEFAULT_BLOCK_R,
                              interpret: Optional[bool] = None):
    """packed (R, d//(8/bits)) u8, scale (R, 1) f32, m (R, d).
    Returns m_new (R, d) f32 — the receiver's reconstructed activation."""
    assert bits in (2, 4, 8), bits
    r, d = m.shape
    k = 8 // bits
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_dua_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d // k), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        name="dequant_unpack_accumulate",
        interpret=_interpret(interpret),
    )(packed, scale, m)


# ---------------------------------------------------------------------------
# DirectQ / backward-gradient / buffer codec: absmax -> quantize -> pack
# ---------------------------------------------------------------------------

def _qp_kernel(x_ref, *rest, bits: int, noise: str):
    u, (packed_ref, scale_ref) = _kernel_noise(noise, rest, x_ref.shape)
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), _EPS)
    packed_ref[...] = _pack(_quant_codes(x, scale, bits, u), bits)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def quantize_pack(x, u=None, *, bits: int, seed=None,
                  block_r: int = DEFAULT_BLOCK_R,
                  interpret: Optional[bool] = None):
    """x: (R, d); u: optional uniform noise (R, d) (or seed: (2,) i32
    for the on-core PRNG path, TPU only).  Returns
    (packed (R, d//(8/bits)) u8, scale (R, 1) f32) — one fused pass for
    the DirectQ sender, backward-gradient quantize, and z-bit buffer
    writes."""
    assert bits in (2, 4, 8), bits
    r, d = x.shape
    k = 8 // bits
    assert d % k == 0, (d, bits)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    nargs, nspecs, noise = _noise_arg(u, seed, row_spec)
    in_specs = [row_spec] + nspecs
    args = [x] + nargs
    return pl.pallas_call(
        functools.partial(_qp_kernel, bits=bits, noise=noise),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((br, d // k), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, d // k), jnp.uint8),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        name="quantize_pack",
        interpret=_interpret(interpret),
    )(*args)

def _ud_kernel(packed_ref, scale_ref, out_ref, *, bits: int):
    codes = _unpack(packed_ref[...], bits)
    out_ref[...] = _dequant(codes, scale_ref[...], bits
                            ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_r", "out_dtype",
                                             "interpret"))
def unpack_dequant(packed, scale, *, bits: int, out_dtype=jnp.float32,
                   block_r: int = DEFAULT_BLOCK_R,
                   interpret: Optional[bool] = None):
    """packed (R, pw) u8, scale (R, 1) f32 -> values (R, pw * 8/bits) in
    out_dtype — one fused pass for the DirectQ/backward receiver and
    z-bit buffer reads."""
    assert bits in (2, 4, 8), bits
    r, pw = packed.shape
    k = 8 // bits
    d = pw * k
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_ud_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, pw), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.dtype(out_dtype)),
        name="unpack_dequant",
        interpret=_interpret(interpret),
    )(packed, scale)


# ---------------------------------------------------------------------------
# DP gradient wire: shared-scale quantize, code-domain psum, sum -> mean
# ---------------------------------------------------------------------------

def _qps_kernel(x_ref, s_ref, *rest, bits: int, stochastic: bool):
    if stochastic:
        u_ref, packed_ref = rest
        u = u_ref[...]
    else:
        (packed_ref,) = rest
        u = None
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(s_ref[...].astype(jnp.float32), _EPS)
    packed_ref[...] = _pack(_quant_codes(x, scale, bits, u), bits)


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def quantize_pack_scaled(x, s, u=None, *, bits: int,
                         block_r: int = DEFAULT_BLOCK_R,
                         interpret: Optional[bool] = None):
    """x: (R, d) values, s: (R, 1) caller-supplied rowwise scale (e.g. the
    pmax-shared scale of a compressed allreduce); u: optional uniform
    noise (R, d).  Returns packed (R, d//(8/bits)) u8 — one fused pass
    for the error-feedback gradient sender."""
    assert bits in (2, 4, 8), bits
    r, d = x.shape
    k = 8 // bits
    assert d % k == 0, (d, bits)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    in_specs = [row_spec, pl.BlockSpec((br, 1), lambda i: (i, 0))]
    args = [x, s]
    if u is not None:
        in_specs.append(row_spec)
        args.append(u)
    return pl.pallas_call(
        functools.partial(_qps_kernel, bits=bits, stochastic=u is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d // k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d // k), jnp.uint8),
        name="quantize_pack_scaled",
        interpret=_interpret(interpret),
    )(*args)


def _uc_kernel(packed_ref, out_ref, *, bits: int):
    out_ref[...] = _unpack(packed_ref[...], bits)


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def unpack_codes(packed, *, bits: int, block_r: int = DEFAULT_BLOCK_R,
                 interpret: Optional[bool] = None):
    """packed (R, pw) u8 -> (R, pw * 8/bits) int32 codes: the code-domain
    form a compressed allreduce accumulates with ``psum`` (int32 sums of
    b-bit codes are exact in any reduction order)."""
    assert bits in (2, 4, 8), bits
    r, pw = packed.shape
    k = 8 // bits
    d = pw * k
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_uc_kernel, bits=bits),
        grid=grid,
        in_specs=[pl.BlockSpec((br, pw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.int32),
        name="unpack_codes",
        interpret=_interpret(interpret),
    )(packed)


def _dsm_kernel(total_ref, s_ref, out_ref, *, bits: int, n: int):
    # mean of n dequantized code tensors, given their exact int32 sum
    out_ref[...] = Q.dequantize_sum_mean(total_ref[...], s_ref[...],
                                         bits, n)


@functools.partial(jax.jit, static_argnames=("bits", "n", "block_r",
                                             "interpret"))
def dequant_sum_mean(total, s, *, bits: int, n: int,
                     block_r: int = DEFAULT_BLOCK_R,
                     interpret: Optional[bool] = None):
    """total (R, d) int32 code sum over n workers, s (R, 1) shared scale.
    Returns the mean gradient (R, d) f32 — the receiver side of the
    compressed DP allreduce."""
    assert bits in (2, 4, 8), bits
    assert isinstance(n, int) and n >= 1, n
    r, d = total.shape
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_dsm_kernel, bits=bits, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        name="dequant_sum_mean",
        interpret=_interpret(interpret),
    )(total, s)


# ---------------------------------------------------------------------------
# compressed ring collective: codes-only encode, unpack-accumulate,
# code-sum pack/unpack (core.collectives.ring_ef_reduce_mean_bucket)
# ---------------------------------------------------------------------------

def _qcs_kernel(x_ref, s_ref, *rest, bits: int, noise: str, pack: bool):
    u, outs = _kernel_noise(noise, rest, x_ref.shape)
    if pack:
        packed_ref, codes_ref = outs
    else:
        (codes_ref,) = outs
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(s_ref[...].astype(jnp.float32), _EPS)
    codes = _quant_codes(x, scale, bits, u)
    if pack:
        packed_ref[...] = _pack(codes, bits)
    codes_ref[...] = codes


@functools.partial(jax.jit, static_argnames=("bits", "pack", "block_r",
                                             "interpret"))
def quantize_codes_scaled(x, s, u=None, *, bits: int, pack: bool = False,
                          seed=None, block_r: int = DEFAULT_BLOCK_R,
                          interpret: Optional[bool] = None):
    """Codes-only encode: quantize x (R, d) against the caller-supplied
    rowwise scale s (R, 1) and emit int32 codes — the accumulator form a
    compressed allreduce sums — WITHOUT the pack→unpack round trip of
    `quantize_pack_scaled` + `unpack_codes`.  With pack=True the same
    pass also emits the packed u8 wire payload (the ring's hop
    segments).  u: optional uniform noise (R, d) (or seed: (2,) i32 for
    the on-core PRNG path, TPU only).

    Returns codes (R, d) i32, or (packed (R, d//(8/bits)) u8, codes)."""
    assert bits in (2, 4, 8), bits
    r, d = x.shape
    k = 8 // bits
    assert d % k == 0, (d, bits)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    row_spec = pl.BlockSpec((br, d), lambda i: (i, 0))
    nargs, nspecs, noise = _noise_arg(u, seed, row_spec)
    in_specs = [row_spec, pl.BlockSpec((br, 1), lambda i: (i, 0))] + nspecs
    args = [x, s] + nargs
    out_specs = [pl.BlockSpec((br, d), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((r, d), jnp.int32)]
    if pack:
        out_specs = [pl.BlockSpec((br, d // k), lambda i: (i, 0))] \
            + out_specs
        out_shape = [jax.ShapeDtypeStruct((r, d // k), jnp.uint8)] \
            + out_shape
    out = pl.pallas_call(
        functools.partial(_qcs_kernel, bits=bits, noise=noise, pack=pack),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="quantize_codes_scaled",
        interpret=_interpret(interpret),
    )(*args)
    return tuple(out) if pack else out[0]


def _ua_kernel(packed_ref, acc_ref, out_ref, *, bits: int):
    out_ref[...] = acc_ref[...] + _unpack(packed_ref[...], bits)


@functools.partial(jax.jit, static_argnames=("bits", "block_r",
                                             "interpret"))
def unpack_accumulate(packed, acc, *, bits: int,
                      block_r: int = DEFAULT_BLOCK_R,
                      interpret: Optional[bool] = None):
    """packed (R, pw) u8 incoming ring segment, acc (R, pw * 8/bits) i32
    local code accumulator.  Returns acc + unpack(packed) in ONE pass —
    the ring's accumulate step (the unpack the psum wire used to run as
    a separate op now rides the accumulation's HBM traffic)."""
    assert bits in (2, 4, 8), bits
    r, pw = packed.shape
    k = 8 // bits
    d = pw * k
    assert acc.shape == (r, d), (acc.shape, r, d)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_ua_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, pw), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.int32),
        name="unpack_accumulate",
        interpret=_interpret(interpret),
    )(packed, acc)


def _sum_geometry(bits: int, n: int) -> int:
    """Sum packing width in bits — mirrors
    core.quantization.sum_wire_bits."""
    maxv = n * _levels(bits)
    for sw in (1, 2, 4, 8, 16, 32):
        if maxv <= (1 << sw) - 1:
            return sw
    raise ValueError((bits, n))


def _ps_kernel(total_ref, out_ref, *, sw: int):
    t = total_ref[...]
    if sw <= 8:
        out_ref[...] = _pack(t, sw)
    else:
        # byte plane b holds the b-th little-endian byte of every sum
        out_ref[...] = _split_planes(t, sw // 8, 8).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bits", "n", "block_r",
                                             "interpret"))
def pack_sums(total, *, bits: int, n: int,
              block_r: int = DEFAULT_BLOCK_R,
              interpret: Optional[bool] = None):
    """total (R, d) i32 code sums over n workers -> dense u8 payload at
    `sum_wire_bits(bits, n)` bits per sum — the ring's all-gather hop
    format (b + ceil(log2 n) bits is the exactness price of shipping
    sums instead of re-quantizing)."""
    assert bits in (2, 4, 8), bits
    sw = _sum_geometry(bits, n)
    r, d = total.shape
    if sw <= 8:
        k = 8 // sw
        assert d % k == 0, (d, sw)
        pw = d // k
    else:
        pw = d * (sw // 8)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_ps_kernel, sw=sw),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, pw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, pw), jnp.uint8),
        name="pack_sums",
        interpret=_interpret(interpret),
    )(total)


def _us_kernel(packed_ref, out_ref, *, sw: int):
    p = packed_ref[...]
    if sw <= 8:
        out_ref[...] = _unpack(p, sw)
    else:
        out_ref[...] = _join_planes(p.astype(jnp.int32), sw // 8, 8)


@functools.partial(jax.jit, static_argnames=("bits", "n", "block_r",
                                             "interpret"))
def unpack_sums(packed, *, bits: int, n: int,
                block_r: int = DEFAULT_BLOCK_R,
                interpret: Optional[bool] = None):
    """Inverse of `pack_sums`: u8 payload -> (R, d) i32 code sums."""
    assert bits in (2, 4, 8), bits
    sw = _sum_geometry(bits, n)
    r, pw = packed.shape
    d = pw * (8 // sw) if sw <= 8 else pw // (sw // 8)
    assert r % block_r == 0 or r < block_r, (r, block_r)
    br = min(block_r, r)
    grid = (r // br,)
    return pl.pallas_call(
        functools.partial(_us_kernel, sw=sw),
        grid=grid,
        in_specs=[pl.BlockSpec((br, pw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.int32),
        name="unpack_sums",
        interpret=_interpret(interpret),
    )(packed)
