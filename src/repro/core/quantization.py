"""Uniform activation quantization used by AQ-SGD and DirectQ.

The paper's Q (§4.1): normalize a vector into [-1, 1] by its absolute
maximum and partition the range uniformly into 2**b intervals
(Chakrabarti & Moseley 2019).  The theory (Thm 3.1) requires Q to be
*unbiased* with relative error ``E||x - Q(x)|| <= c_Q ||x||`` — satisfied
here by stochastic rounding on the uniform grid (the grid always covers
the input because the scale is the absmax).

Two forms are provided:

* ``quantize`` / ``dequantize`` / ``pack_codes`` / ``unpack_codes`` — the
  *wire* form.  Codes are uint8 (2/4/8 bits packed densely) plus a float
  scale per row; this is the payload that actually crosses the pipeline
  boundary (``ppermute``), so compiled collective bytes shrink by the
  true compression ratio.
* ``qdq`` — quantize→dequantize "fake quant" used by the bit-faithful
  simulated trainer; numerically identical to a wire round-trip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_EPS = 1e-12


def absmax_scale(x: jax.Array, per_row: bool = True) -> jax.Array:
    """Positive scale such that x/scale ∈ [-1, 1].

    per_row=True gives one scale per trailing-dim row (the paper's
    per-vector normalization); False gives a single per-tensor scale.
    """
    x = x.astype(jnp.float32)
    if per_row:
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    else:
        s = jnp.max(jnp.abs(x))
    return jnp.maximum(s, _EPS)


def _grid_positions(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Map x into continuous grid coordinates [0, 2**bits - 1]."""
    levels = (1 << bits) - 1
    y = (x.astype(jnp.float32) / scale + 1.0) * (0.5 * levels)
    return jnp.clip(y, 0.0, float(levels))


def quantize(
    x: jax.Array,
    bits: int,
    *,
    stochastic: bool = True,
    key: Optional[jax.Array] = None,
    per_row: bool = True,
    scale: Optional[jax.Array] = None,
    noise: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantize to uint8 codes in [0, 2**bits - 1] plus float32 scale.

    Stochastic rounding draws from `key`, or consumes pre-drawn uniform
    `noise` of x.shape (``noise < frac`` is exactly what bernoulli(key,
    frac) computes, so both routes are bit-identical for the same key —
    the noise route is what keeps the Pallas backend in lockstep)."""
    assert 1 <= bits <= 8, bits
    if scale is None:
        scale = absmax_scale(x, per_row=per_row)
    y = _grid_positions(x, scale, bits)
    if stochastic:
        lo = jnp.floor(y)
        frac = y - lo
        if noise is not None:
            bump = (noise < frac).astype(jnp.float32)
        elif key is not None:
            bump = jax.random.bernoulli(key, frac).astype(jnp.float32)
        else:
            raise ValueError("stochastic quantization needs a PRNG key "
                             "or a uniform noise tensor")
        codes = lo + bump
    else:
        codes = jnp.round(y)
    return codes.astype(jnp.uint8), scale


def dequantize_sum_mean(total: jax.Array, scale: jax.Array, bits: int,
                        n: int) -> jax.Array:
    """Mean of n dequantized code tensors, given their exact int32 sum
    under one shared scale: mean_i ((2 c_i - lv) s) / lv
    = ((2 T - n lv) s) / (n lv).  n = 1 is `dequantize`.

    Plain jnp, so the Pallas kernels call it inside their bodies too:
    this is the one place both boundary backends take the formula from."""
    # ((2T - n*lv) * scale) * f32(1/(n*lv)), in this exact association:
    # 2T - n*lv is integer-exact in f32, and the rest is two
    # multiplications, which every backend rounds the same way (IEEE).
    # A division by the constant n*lv is NOT: on a TPU, XLA and Mosaic
    # round x / 255 differently.  The bit-identical reference/pallas
    # boundary backend contract depends on this shape; don't "simplify"
    # it to (c * (2/levels) - 1) * scale.
    levels = n * ((1 << bits) - 1)
    ic = total.astype(jnp.float32) * 2.0 - float(levels)
    return (ic * scale) * (1.0 / levels)


def dequantize(codes: jax.Array, scale: jax.Array, bits: int,
               dtype: jnp.dtype = jnp.float32) -> jax.Array:
    """Map b-bit codes back to values: the center of each grid cell,
    scaled — the inverse the whole parity contract rounds through."""
    return dequantize_sum_mean(codes, scale, bits, 1).astype(dtype)


def qdq(
    x: jax.Array,
    bits: int,
    *,
    stochastic: bool = True,
    key: Optional[jax.Array] = None,
    per_row: bool = True,
) -> jax.Array:
    """Fake-quantization round trip; preserves input dtype."""
    codes, scale = quantize(x, bits, stochastic=stochastic, key=key,
                            per_row=per_row)
    return dequantize(codes, scale, bits, dtype=x.dtype)


# ---------------------------------------------------------------------------
# Dense bit-packing — the wire format.
# ---------------------------------------------------------------------------

def codes_per_byte(bits: int) -> int:
    """How many b-bit codes pack into one wire byte (byte-aligned
    widths only)."""
    assert bits in (1, 2, 4, 8), f"packing supports 1/2/4/8 bits, got {bits}"
    return 8 // bits


def packed_width(n: int, bits: int) -> int:
    """Packed bytes per row.  Byte-aligned (1/2/4/8 bit) formats pack k
    codes/byte; other widths (e.g. the paper's fw3/bw6) are bit-packed —
    width is ceil(n*bits/8)."""
    if bits in (1, 2, 4, 8):
        k = codes_per_byte(bits)
        return (n + k - 1) // k
    return (n * bits + 7) // 8


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """Pack uint8 codes (< 2**bits) densely along the last axis, planar:
    a row of n codes is zero-padded to k*pw (k = 8/bits, pw =
    ceil(n/k)), and byte j holds codes j, j+pw, ..., j+(k-1)*pw at bit
    shifts 0, bits, ..., (k-1)*bits (docs/WIRE_FORMATS.md)."""
    k = codes_per_byte(bits)
    if k == 1:
        return codes
    n = codes.shape[-1]
    pad = (-n) % k
    if pad:
        codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, pad)])
    planes = codes.reshape(*codes.shape[:-1], k, -1).astype(jnp.uint32)
    shifts = (jnp.arange(k, dtype=jnp.uint32) * bits)[:, None]
    packed = jnp.sum(planes << shifts, axis=-2)
    return packed.astype(jnp.uint8)


def unpack_codes(packed: jax.Array, bits: int, n: int) -> jax.Array:
    """Inverse of pack_codes; n = original last-axis length."""
    k = codes_per_byte(bits)
    if k == 1:
        return packed[..., :n]
    shifts = (jnp.arange(k, dtype=jnp.uint32) * bits)[:, None]
    mask = jnp.uint32((1 << bits) - 1)
    vals = (packed[..., None, :].astype(jnp.uint32) >> shifts) & mask
    flat = vals.reshape(*packed.shape[:-1], -1)
    return flat[..., :n].astype(jnp.uint8)


def wire_bytes(shape: tuple[int, ...], bits: int,
               scale_bytes: int = 4) -> int:
    """Bytes on the wire for a quantized tensor with per-row scales."""
    *rows, n = shape
    nrows = int(functools.reduce(lambda a, b: a * b, rows, 1))
    return nrows * packed_width(n, bits) + nrows * scale_bytes


# ---------------------------------------------------------------------------
# Code-SUM packing — the all-gather half of the compressed ring collective.
#
# A sum of n b-bit codes is at most n*(2**b - 1): it no longer fits b bits,
# but it fits b + ceil(log2 n) — the log2(n) growth is the price of keeping
# the ring bit-identical to ``psum(int32 codes)`` (re-quantizing the mean
# would stay at b bits both phases but double-quantizes, breaking the
# parity anchor).  Sums are packed densely at the narrowest supported
# width: sub-byte widths reuse the dense code packer, 16/32-bit widths
# split little-endian into u8 wire bytes.
# ---------------------------------------------------------------------------

SUM_WIRE_WIDTHS = (1, 2, 4, 8, 16, 32)


def sum_wire_bits(bits: int, n: int) -> int:
    """Narrowest packing width (in bits) holding any sum of n b-bit codes."""
    assert n >= 1 and 1 <= bits <= 8, (bits, n)
    maxv = n * ((1 << bits) - 1)
    for sw in SUM_WIRE_WIDTHS:
        if maxv <= (1 << sw) - 1:
            return sw
    raise ValueError(f"code sums for bits={bits}, n={n} exceed 32 bits")


def sum_packed_width(d: int, bits: int, n: int) -> int:
    """Packed wire bytes per row of d code sums over n workers."""
    sw = sum_wire_bits(bits, n)
    if sw <= 8:
        k = 8 // sw
        return (d + k - 1) // k
    return d * (sw // 8)


def pack_sums(total: jax.Array, bits: int, n: int) -> jax.Array:
    """int32 code sums over n workers -> dense u8 payload
    (`sum_wire_bits(bits, n)` bits per sum along the last axis).  Widths
    above 8 bits are byte-planar: plane b of a row (bytes b*d ...
    (b+1)*d) holds the b-th little-endian byte of every sum."""
    sw = sum_wire_bits(bits, n)
    if sw <= 8:
        # sums < 2**sw <= 256 by construction: the code packer applies
        return pack_codes(total.astype(jnp.uint8), sw)
    nb = sw // 8
    t = total.astype(jnp.uint32)
    shifts = (jnp.arange(nb, dtype=jnp.uint32) * 8)[:, None]
    b = (t[..., None, :] >> shifts) & jnp.uint32(0xFF)
    return b.reshape(*t.shape[:-1], -1).astype(jnp.uint8)


def unpack_sums(packed: jax.Array, bits: int, n: int, d: int) -> jax.Array:
    """Inverse of `pack_sums`; d = original last-axis length.  int32."""
    sw = sum_wire_bits(bits, n)
    if sw <= 8:
        return unpack_codes(packed, sw, d).astype(jnp.int32)
    nb = sw // 8
    shifts = (jnp.arange(nb, dtype=jnp.uint32) * 8)[:, None]
    b = packed.astype(jnp.uint32).reshape(*packed.shape[:-1], nb, -1)
    vals = jnp.sum(b << shifts, axis=-2)
    return vals[..., :d].astype(jnp.int32)


__all__ = [
    "absmax_scale", "quantize", "dequantize", "qdq",
    "codes_per_byte", "packed_width", "pack_codes", "unpack_codes",
    "wire_bytes",
    "sum_wire_bits", "sum_packed_width", "pack_sums", "unpack_sums",
]
