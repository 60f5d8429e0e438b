"""Backend-selectable AQ-SGD boundary ops — the ONE hot path.

Every wire crossing in the system goes through the ops below, each
available on two bit-identical backends: the activation boundaries
(AQ-SGD sender/receiver, DirectQ, backward-gradient quantize, z-bit
buffer codec via `encode_delta`/`decode_accumulate`/`encode`/`decode`)
and the data-parallel gradient wire — the shared-scale
compressed-allreduce codec behind `core.grad_compress` and
`core.collectives`: `encode_codes_with_scale` (the ONE sender entry
point: int32 accumulator codes, plus the packed ring payload with
pack=True), `accumulate_codes` (the ring's fused unpack-accumulate),
`pack_sums`/`unpack_sums` (the ring's packed code-sum all-gather),
`decode_sum_mean` (the receiver), and the legacy
`encode_with_scale`/`decode_codes` pair:

* ``"pallas"``    — the fused TPU kernels in `repro.kernels.quant_pack`:
  one HBM pass per side instead of the ~6 round-trips of the unfused
  chain (paper §3.3's "compression is free" claim lives or dies here);
* ``"reference"`` — the pure-jnp chain over `repro.core.quantization`,
  kept as the correctness oracle and the fast path on CPU containers
  where Pallas only runs in interpret mode.

``"auto"`` (the default everywhere) resolves to pallas on TPU and
reference otherwise; REPRO_BOUNDARY_BACKEND overrides.  The contract
that the two backends are bit-identical — codes, scales, m_new, and
backward gradients — is enforced by tests/test_boundary_parity.py.

Stochastic rounding draws ONE uniform tensor here and feeds it to
either backend, so the wire payload and message buffers never depend on
the backend.  Scope note: the contract is per-op (same inputs -> same
bits).  Whole-model training trajectories may still drift at the ulp
level between backends, because swapping an opaque pallas_call for a
jnp chain changes how XLA fuses the SURROUNDING model ops — the same
class of drift as changing XLA versions, and statistically irrelevant
to convergence (fp32 runs are bit-equal; compressed runs track to
print precision — see the quickstart).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import env
from repro.core import quantization as Q
from repro.kernels import ops as K

BACKENDS = ("reference", "pallas")
PACKABLE_BITS = (1, 2, 4, 8)       # dense byte-aligned wire packing
KERNEL_BITS = (2, 4, 8)            # widths the fused kernels implement


def resolve_backend(backend: str = "auto", bits: Optional[int] = None) \
        -> str:
    """'auto' -> REPRO_BOUNDARY_BACKEND, else pallas iff running on TPU
    (interpret-mode pallas on CPU is a debugging path, not a hot path).

    Widths outside KERNEL_BITS (the paper's fw3/bw6 ablations) always
    resolve to the reference chain — they are simulation-only."""
    if bits is not None and bits not in KERNEL_BITS:
        return "reference"
    if backend == "auto":
        override = env.boundary_backend_override()
        if override:
            backend = override
        else:
            backend = "pallas" if jax.default_backend() == "tpu" \
                else "reference"
    assert backend in BACKENDS, backend
    return backend


def _noise(shape, stochastic: bool, key) -> Optional[jax.Array]:
    if not stochastic:
        return None
    if key is None:
        raise ValueError("stochastic boundary ops need a PRNG key")
    return jax.random.uniform(key, shape, jnp.float32)


def oncore_prng_enabled() -> bool:
    """REPRO_ONCORE_PRNG=1 opts the pallas encode kernels into drawing
    stochastic-rounding noise from the on-core PRNG instead of an HBM
    noise tensor.  TPU-only (interpret mode cannot lower prng_seed) and
    it relaxes the ref↔pallas parity contract to a STATISTICAL one —
    gated by the 10k-trial unbiasedness test in test_grad_compress.py."""
    return env.oncore_prng()


def _stochastic_args(shape, stochastic: bool, key, backend: str,
                     noise=None):
    """(noise tensor, on-core seed) for an encode op: exactly one is
    non-None when stochastic.  The seed path activates only for the
    pallas backend under the REPRO_ONCORE_PRNG opt-in."""
    if not stochastic:
        return None, None
    if noise is not None:
        return noise, None
    if backend == "pallas" and oncore_prng_enabled():
        if not K.oncore_prng_supported():
            raise ValueError(
                "REPRO_ONCORE_PRNG=1 but the on-core PRNG cannot lower "
                "on this backend (CPU interpret mode has no prng_seed); "
                "unset it or run on TPU")
        if key is None:
            raise ValueError("stochastic boundary ops need a PRNG key")
        k = jnp.asarray(key).reshape(-1)[-2:]
        return None, jax.lax.bitcast_convert_type(k, jnp.int32)
    return _noise(shape, stochastic, key), None


def encode_delta(a, m, *, bits: int, stochastic: bool = False, key=None,
                 backend: str = "auto"):
    """AQ-SGD sender: (a, m) -> (packed u8 (..., pw), scale f32 (..., 1),
    m_new f32 (..., d)) with m_new = m + dequant(codes) — the wire
    payload plus the updated message buffer, in one fused pass.

    Non-byte-aligned widths (fw3/bw6 ablations) are simulation-only:
    payload is the raw u8 codes, never densely packed."""
    backend = resolve_backend(backend, bits)
    u, seed = _stochastic_args(a.shape, stochastic, key, backend)
    if backend == "pallas":
        return K.boundary_compress(a, m, u, bits=bits, seed=seed)
    a32 = a.astype(jnp.float32)
    m32 = m.astype(jnp.float32)
    codes, scale = Q.quantize(a32 - m32, bits, stochastic=stochastic,
                              noise=u)
    packed = Q.pack_codes(codes, bits) if bits in PACKABLE_BITS else codes
    m_new = m32 + Q.dequantize(codes, scale, bits)
    return packed, scale, m_new


def decode_accumulate(packed, scale, m, *, bits: int,
                      backend: str = "auto"):
    """AQ-SGD receiver: m_new f32 = m + dequant(unpack(packed)).  Applies
    the SAME quantized delta as the sender, so both buffer replicas stay
    bit-identical (Algorithm 2)."""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.boundary_decompress(packed, scale, m, bits=bits)
    d = m.shape[-1]
    codes = Q.unpack_codes(packed, bits, d) if bits in PACKABLE_BITS \
        else packed
    return m.astype(jnp.float32) + Q.dequantize(codes, scale, bits)


def encode(x, *, bits: int, stochastic: bool = False, key=None,
           backend: str = "auto"):
    """Direct quantize-and-pack: (packed u8 (..., pw), scale f32).  Used
    by the DirectQ sender, the backward-gradient wire, and z-bit buffer
    writes.  Non-byte-aligned widths return raw u8 codes (simulation
    only)."""
    backend = resolve_backend(backend, bits)
    u, seed = _stochastic_args(x.shape, stochastic, key, backend)
    if backend == "pallas":
        return K.quantize_pack(x, u, bits=bits, seed=seed)
    codes, scale = Q.quantize(x.astype(jnp.float32), bits,
                              stochastic=stochastic, noise=u)
    packed = Q.pack_codes(codes, bits) if bits in PACKABLE_BITS else codes
    return packed, scale


def decode(packed, scale, *, bits: int, d: int, dtype=jnp.float32,
           backend: str = "auto"):
    """Inverse of `encode`: (..., pw) u8 + scales -> (..., d) values."""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        out = K.unpack_dequant(packed, scale, bits=bits, out_dtype=dtype)
        return out[..., :d]
    codes = Q.unpack_codes(packed, bits, d) if bits in PACKABLE_BITS \
        else packed
    return Q.dequantize(codes, scale, bits, dtype)


def encode_with_scale(x, scale, *, bits: int, stochastic: bool = False,
                      key=None, noise=None, backend: str = "auto"):
    """Quantize with a caller-supplied rowwise scale and pack: the DP
    gradient-wire sender.  In a compressed allreduce every worker
    quantizes against the SAME (pmax-shared) scale so that the psum of
    codes dequantizes to the exact mean; the scale is therefore an input
    here, never computed.  Returns packed u8 (..., pw) (raw u8 codes for
    non-byte-aligned widths, simulation only)."""
    backend = resolve_backend(backend, bits)
    # clamp once for BOTH backends: the pallas kernel clamps internally,
    # so an unclamped zero scale would NaN only the reference chain and
    # break the bit-identity contract
    scale = jnp.maximum(scale.astype(jnp.float32), Q._EPS)
    u = noise if noise is not None else _noise(x.shape, stochastic, key)
    if backend == "pallas":
        return K.quantize_pack_scaled(x, scale, u, bits=bits)
    codes, _ = Q.quantize(x.astype(jnp.float32), bits,
                          stochastic=stochastic, noise=u, scale=scale)
    return Q.pack_codes(codes, bits) if bits in PACKABLE_BITS else codes


def decode_codes(packed, *, bits: int, d: int, backend: str = "auto"):
    """Wire payload -> int32 codes: the accumulator form a compressed
    allreduce ships through ``psum`` (int32 sums of b-bit codes are
    exact in every reduction order, which is what makes the distributed
    wire bit-identical to the single-process simulation)."""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.unpack_codes(packed, bits=bits)[..., :d]
    codes = Q.unpack_codes(packed, bits, d) if bits in PACKABLE_BITS \
        else packed
    return codes.astype(jnp.int32)


def decode_sum_mean(total, scale, *, bits: int, n: int,
                    backend: str = "auto"):
    """Int32 code sum over n workers + shared rowwise scale -> mean
    values: the DP gradient-wire receiver.  n must be static (the mesh
    size)."""
    assert isinstance(n, int) and n >= 1, n
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.dequant_sum_mean(total, scale, bits=bits, n=n)
    return Q.dequantize_sum_mean(total, scale, bits, n)


def encode_codes_with_scale(x, scale, *, bits: int, stochastic: bool = False,
                            key=None, noise=None, pack: bool = False,
                            backend: str = "auto"):
    """Codes-only encode against a caller-supplied rowwise scale: the ONE
    sender entry point of the compressed DP allreduce (psum wire, ring
    wire, and the simulator all route here).

    Returns int32 codes (..., d) — the accumulator form — without the
    on-device pack→unpack round trip the old `encode_with_scale` +
    `decode_codes` pair paid.  pack=True additionally emits the packed
    u8 wire payload in the SAME fused pass: (packed, codes) — that is
    the ring sender, whose packed segments genuinely ship.

    Non-byte-aligned widths (simulation-only) return raw u8 codes as
    the payload when pack=True."""
    backend = resolve_backend(backend, bits)
    scale = jnp.maximum(scale.astype(jnp.float32), Q._EPS)
    u, seed = _stochastic_args(x.shape, stochastic, key, backend,
                               noise=noise)
    if backend == "pallas":
        return K.quantize_codes_scaled(x, scale, u, bits=bits, pack=pack,
                                       seed=seed)
    codes, _ = Q.quantize(x.astype(jnp.float32), bits,
                          stochastic=stochastic, noise=u, scale=scale)
    icodes = codes.astype(jnp.int32)
    if pack:
        packed = Q.pack_codes(codes, bits) if bits in PACKABLE_BITS \
            else codes
        return packed, icodes
    return icodes


def accumulate_codes(packed, acc, *, bits: int, backend: str = "auto"):
    """Ring accumulate step: acc + unpack(packed) in one fused int32
    pass — the local accumulation that replaces the psum's i32 lanes
    (int32 adds are exact in any order, which is what keeps the ring
    bit-identical to `psum(codes)`)."""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.unpack_accumulate(packed, acc, bits=bits)
    d = acc.shape[-1]
    codes = Q.unpack_codes(packed, bits, d) if bits in PACKABLE_BITS \
        else packed
    return acc + codes.astype(jnp.int32)


def pack_sums(total, *, bits: int, n: int, backend: str = "auto"):
    """Pack int32 code sums over n workers densely at
    `Q.sum_wire_bits(bits, n)` bits — the ring's all-gather payload.
    (b + ceil(log2 n) bits per element is the exactness price: shipping
    sums keeps the ring bit-identical to the psum wire, where
    re-quantizing the mean to b bits would not.)"""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.pack_sums(total, bits=bits, n=n)
    return Q.pack_sums(total, bits, n)


def unpack_sums(packed, *, bits: int, n: int, d: int,
                backend: str = "auto"):
    """Inverse of `pack_sums`: u8 payload -> (..., d) int32 code sums."""
    backend = resolve_backend(backend, bits)
    if backend == "pallas":
        return K.unpack_sums(packed, bits=bits, n=n)[..., :d]
    return Q.unpack_sums(packed, bits, n, d)


def roundtrip(x, *, bits: int, stochastic: bool = False, key=None,
              backend: str = "auto"):
    """encode -> decode in x.dtype: the wire-faithful fake quant used for
    backward gradients and DirectQ (== Q.qdq on the reference backend,
    fused on pallas)."""
    packed, scale = encode(x, bits=bits, stochastic=stochastic, key=key,
                           backend=backend)
    return decode(packed, scale, bits=bits, d=x.shape[-1], dtype=x.dtype,
                  backend=backend)
