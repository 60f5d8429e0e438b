"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth
swept against in tests/test_kernels.py).  The byte layout is the one
`repro.core.quantization` defines; the oracles pack through it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quantization as Q

_EPS = 1e-12


def _codes_ref(x, scale, bits: int, u=None):
    levels = (1 << bits) - 1
    y = jnp.clip((x / scale + 1.0) * (0.5 * levels), 0.0, levels)
    if u is None:
        return jnp.round(y).astype(jnp.uint8)
    lo = jnp.floor(y)
    return (lo + (u < (y - lo)).astype(jnp.float32)).astype(jnp.uint8)


def delta_quantize_pack_ref(a, m, bits: int, u=None):
    """AQ-SGD sender side: delta -> rowwise absmax scale -> b-bit codes ->
    dense uint8 packing.  a, m: (R, d) float; u: optional uniform noise
    for stochastic rounding.  Returns (packed (R, d*b/8), scale (R, 1)
    f32, m_new (R, d) f32)."""
    delta = a.astype(jnp.float32) - m.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(delta), axis=-1, keepdims=True),
                        _EPS)
    codes = _codes_ref(delta, scale, bits, u)
    packed = Q.pack_codes(codes, bits)
    m_new = m.astype(jnp.float32) + Q.dequantize(codes, scale, bits)
    return packed, scale, m_new


def quantize_pack_ref(x, bits: int, u=None):
    """DirectQ/backward/buffer sender side: absmax -> codes -> packing."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), _EPS)
    return Q.pack_codes(_codes_ref(x, scale, bits, u), bits), scale


def unpack_dequant_ref(packed, scale, bits: int):
    """Inverse of quantize_pack_ref (full packed width, no accumulate)."""
    return dequant_unpack_accumulate_ref(
        packed, scale, jnp.zeros((packed.shape[0],
                                  packed.shape[1] * (8 // bits))), bits)


def dequant_unpack_accumulate_ref(packed, scale, m, bits: int):
    """AQ-SGD receiver side: unpack -> dequantize -> m += delta.
    packed: (R, d*b/8) u8; scale (R, 1); m (R, d).  Returns m_new f32."""
    codes = Q.unpack_codes(packed, bits, m.shape[-1])
    return m.astype(jnp.float32) + Q.dequantize(codes, scale, bits)


def quantize_pack_scaled_ref(x, s, bits: int, u=None):
    """DP-gradient sender side: quantize with the caller-supplied
    (pmax-shared) rowwise scale, then pack.  Returns packed u8 only —
    the scale already lives on every worker."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(s.astype(jnp.float32), _EPS)
    return Q.pack_codes(_codes_ref(x, scale, bits, u), bits)


def unpack_codes_ref(packed, bits: int):
    """Wire payload -> int32 codes (the psum accumulator form)."""
    d = packed.shape[-1] * (8 // bits)
    return Q.unpack_codes(packed, bits, d).astype(jnp.int32)


def quantize_codes_scaled_ref(x, s, bits: int, u=None, pack: bool = False):
    """Codes-only encode oracle: quantize against the supplied (shared)
    scale, emit int32 codes — and, with pack=True, also the packed u8
    wire payload (the ring sender's one-pass output)."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(s.astype(jnp.float32), _EPS)
    codes = _codes_ref(x, scale, bits, u)
    if pack:
        return Q.pack_codes(codes, bits), codes.astype(jnp.int32)
    return codes.astype(jnp.int32)


def unpack_accumulate_ref(packed, acc, bits: int):
    """Ring accumulate oracle: acc + unpack(packed) in int32."""
    return acc.astype(jnp.int32) + unpack_codes_ref(packed, bits)


def pack_sums_ref(total, bits: int, n: int):
    """Code-sum packing oracle: i32 sums over n workers -> u8 payload at
    the narrowest width holding n*(2**bits - 1)."""
    return Q.pack_sums(total, bits, n)


def unpack_sums_ref(packed, bits: int, n: int):
    """Inverse of pack_sums_ref (full packed width)."""
    sw = Q.sum_wire_bits(bits, n)
    pw = packed.shape[-1]
    d = pw * (8 // sw) if sw <= 8 else pw // (sw // 8)
    return Q.unpack_sums(packed, bits, n, d)


def dequant_sum_mean_ref(total, s, bits: int, n: int):
    """Int32 code sum over n workers + shared scale -> mean gradient."""
    return Q.dequantize_sum_mean(total, s, bits, n)

