"""jit'd public wrappers around the Pallas kernels.

The kernels compile through Mosaic on a TPU and run in interpret mode
on any other backend (`repro.env.pallas_interpret`, asked at the first
kernel call — importing this module initializes no backend).

The wrappers flatten leading dims to the kernel's (rows, d) layout and
zero-pad ragged row counts up to a block multiple (padding rows are
independent under rowwise quantization and sliced off the outputs), so
callers may pass any (..., d) batch shape.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import quant_pack as _qp


@functools.lru_cache(maxsize=1)
def oncore_prng_supported() -> bool:
    """Whether the opt-in on-core PRNG encode path can lower here.

    Interpret mode has no lowering for pltpu.prng_seed, so off a TPU
    this is False and the boundary layer refuses the REPRO_ONCORE_PRNG
    opt-in with a clear error instead of a lowering crash.  Only that
    error is caught: on a TPU a Mosaic failure raises as one."""
    try:
        x = jnp.zeros((8, 128), jnp.float32)
        _qp.quantize_codes_scaled(
            x, jnp.ones((8, 1), jnp.float32),
            bits=8, seed=jnp.zeros((2,), jnp.int32)).block_until_ready()
        return True
    except NotImplementedError as e:
        # interpret mode: "MLIR translation rule for primitive
        # 'prng_seed' not found for platform cpu"
        if "prng_seed" not in str(e):
            raise
        return False


def _padded_rows(r: int, block_r: int) -> int:
    """Row count the kernel grid actually runs: a multiple of block_r
    (or of the 8-row f32 sublane when everything fits one block)."""
    if r >= block_r:
        return -(-r // block_r) * block_r
    return -(-r // 8) * 8


def _as_rows(x, d: int, block_r: int):
    """(..., d) -> (padded_rows, d) plus the live row count."""
    x2 = x.reshape(-1, d)
    r = x2.shape[0]
    rp = _padded_rows(r, block_r)
    if rp != r:
        x2 = jnp.pad(x2, ((0, rp - r), (0, 0)))
    return x2, r


def boundary_compress(a, m, u=None, *, bits: int, seed=None,
                      block_r: int = 128):
    """Sender side of an AQ-SGD boundary: (a, m) -> (packed, scale, m_new).
    a, m (and optional stochastic noise u): any (..., d).  seed: (2,)
    i32 selects the on-core PRNG path (TPU only) instead of u."""
    shape = a.shape
    d = shape[-1]
    a2, r = _as_rows(a, d, block_r)
    m2, _ = _as_rows(m, d, block_r)
    u2 = None if u is None else _as_rows(u, d, block_r)[0]
    packed, scale, m_new = _qp.delta_quantize_pack(
        a2, m2, u2, bits=bits, seed=seed, block_r=block_r)
    return (packed[:r].reshape(*shape[:-1], -1),
            scale[:r].reshape(*shape[:-1], 1),
            m_new[:r].reshape(shape))


def boundary_decompress(packed, scale, m, *, bits: int,
                        block_r: int = 128):
    """Receiver side: reconstruct m_new = m + dequant(unpack(packed))."""
    shape = m.shape
    d = shape[-1]
    p2, r = _as_rows(packed, packed.shape[-1], block_r)
    s2, _ = _as_rows(scale, 1, block_r)
    m2, _ = _as_rows(m, d, block_r)
    out = _qp.dequant_unpack_accumulate(
        p2, s2, m2, bits=bits, block_r=block_r)
    return out[:r].reshape(shape)


def quantize_pack(x, u=None, *, bits: int, seed=None, block_r: int = 128):
    """Fused absmax -> quantize -> pack for any (..., d) tensor: the
    DirectQ sender, backward-gradient quantize, and z-bit buffer write.
    seed: (2,) i32 selects the on-core PRNG path (TPU only)."""
    shape = x.shape
    d = shape[-1]
    x2, r = _as_rows(x, d, block_r)
    u2 = None if u is None else _as_rows(u, d, block_r)[0]
    packed, scale = _qp.quantize_pack(x2, u2, bits=bits, seed=seed,
                                      block_r=block_r)
    return (packed[:r].reshape(*shape[:-1], -1),
            scale[:r].reshape(*shape[:-1], 1))


def unpack_dequant(packed, scale, *, bits: int, out_dtype=jnp.float32,
                   block_r: int = 128):
    """Fused unpack -> dequantize; inverse of quantize_pack."""
    shape = packed.shape
    pw = shape[-1]
    p2, r = _as_rows(packed, pw, block_r)
    s2, _ = _as_rows(scale, 1, block_r)
    out = _qp.unpack_dequant(p2, s2, bits=bits, out_dtype=out_dtype,
                             block_r=block_r)
    return out[:r].reshape(*shape[:-1], out.shape[-1])


def quantize_pack_scaled(x, s, u=None, *, bits: int, block_r: int = 128):
    """Fused quantize-with-given-scale -> pack for any (..., d) tensor:
    the DP gradient-wire sender (scale is the pmax-shared rowwise scale
    of a compressed allreduce, so it is an input, not computed here)."""
    shape = x.shape
    d = shape[-1]
    x2, r = _as_rows(x, d, block_r)
    s2, _ = _as_rows(s, 1, block_r)
    u2 = None if u is None else _as_rows(u, d, block_r)[0]
    packed = _qp.quantize_pack_scaled(x2, s2, u2, bits=bits,
                                      block_r=block_r)
    return packed[:r].reshape(*shape[:-1], -1)


def unpack_codes(packed, *, bits: int, block_r: int = 128):
    """Fused unpack to int32 codes for any (..., pw) payload — the
    code-domain form the gradient wire accumulates with ``psum``."""
    shape = packed.shape
    p2, r = _as_rows(packed, shape[-1], block_r)
    out = _qp.unpack_codes(p2, bits=bits, block_r=block_r)
    return out[:r].reshape(*shape[:-1], out.shape[-1])


def quantize_codes_scaled(x, s, u=None, *, bits: int, pack: bool = False,
                          seed=None, block_r: int = 128):
    """Codes-only encode for any (..., d) tensor: quantize against the
    supplied (pmax-shared) rowwise scale and emit the int32 accumulator
    codes — with pack=True the same pass also emits the packed u8 wire
    payload (ring sender).  seed: (2,) i32 selects the on-core PRNG
    path (TPU only) instead of an explicit noise tensor."""
    shape = x.shape
    d = shape[-1]
    x2, r = _as_rows(x, d, block_r)
    s2, _ = _as_rows(s, 1, block_r)
    u2 = None if u is None else _as_rows(u, d, block_r)[0]
    out = _qp.quantize_codes_scaled(x2, s2, u2, bits=bits, pack=pack,
                                    seed=seed, block_r=block_r)
    if pack:
        packed, codes = out
        return (packed[:r].reshape(*shape[:-1], -1),
                codes[:r].reshape(shape))
    return out[:r].reshape(shape)


def unpack_accumulate(packed, acc, *, bits: int, block_r: int = 128):
    """Fused unpack + int32 accumulate for any (..., pw) payload — the
    ring's accumulate step.  acc: (..., pw * 8/bits) i32.  Padded rows
    accumulate zeros and are sliced off, so ragged (last) ring segments
    are safe."""
    shape = acc.shape
    p2, r = _as_rows(packed, packed.shape[-1], block_r)
    a2, _ = _as_rows(acc, acc.shape[-1], block_r)
    out = _qp.unpack_accumulate(p2, a2, bits=bits, block_r=block_r)
    return out[:r].reshape(shape)


def pack_sums(total, *, bits: int, n: int, block_r: int = 128):
    """Dense code-sum packing for any (..., d) i32 sum tensor — the
    ring's all-gather payload (`Q.sum_wire_bits(bits, n)` bits/sum)."""
    shape = total.shape
    t2, r = _as_rows(total, shape[-1], block_r)
    out = _qp.pack_sums(t2, bits=bits, n=n, block_r=block_r)
    return out[:r].reshape(*shape[:-1], out.shape[-1])


def unpack_sums(packed, *, bits: int, n: int, block_r: int = 128):
    """Inverse of `pack_sums` for any (..., pw) payload."""
    shape = packed.shape
    p2, r = _as_rows(packed, shape[-1], block_r)
    out = _qp.unpack_sums(p2, bits=bits, n=n, block_r=block_r)
    return out[:r].reshape(*shape[:-1], out.shape[-1])


def dequant_sum_mean(total, s, *, bits: int, n: int, block_r: int = 128):
    """Fused int32-code-sum -> mean values for any (..., d) sum tensor:
    the DP gradient-wire receiver (padded rows carry zero scales and are
    sliced off, so ragged gradient buckets are safe)."""
    shape = total.shape
    d = shape[-1]
    t2, r = _as_rows(total, d, block_r)
    s2, _ = _as_rows(s, 1, block_r)
    out = _qp.dequant_sum_mean(t2, s2, bits=bits, n=n, block_r=block_r)
    return out[:r].reshape(shape)

