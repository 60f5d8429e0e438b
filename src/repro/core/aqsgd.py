"""AQ-SGD: activation-delta compression at pipeline boundaries.

Implements Algorithm 1/2 of the paper in functional JAX form:

* per-(boundary, sample) message buffers ``m(ξ)`` — both sides of a real
  boundary keep bit-identical copies because both apply the *same*
  quantized delta; functionally we carry one logical buffer;
* first-visit sends full precision (``seen`` mask);
* later visits send ``Q(a(ξ, x_t) − m(ξ))`` and update
  ``m(ξ) ← m(ξ) + Q(·)``;
* machine b computes on ``m(ξ)``, i.e. the boundary is a straight-through
  estimator: forward value = m, backward gradient = Q_bw(∇) routed to
  machine a's activation (custom_vjp below);
* the buffer itself may be stored in z bits (paper §H.5,
  "number of bits for previous messages").

``DirectQ`` (AC-GC / TinyScript style, the paper's baseline) and ``fp32``
(no compression) share the same interface.

All quantize/pack/unpack work routes through `repro.core.boundary`, the
backend-selectable fused boundary op (``backend="pallas"`` on TPU,
``"reference"`` jnp chain otherwise); the two backends are bit-identical
by contract, so ``backend`` never changes the trained model.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import tracing
from repro.core import boundary as B
from repro.core import quantization as Q


@dataclass(frozen=True)
class CompressionConfig:
    """Activation-boundary compression knobs (see README "Which knob
    do I turn"): the algorithm on the pipeline axis, code widths, the
    optional z-bit stored-message format, and the codec backend."""
    mode: str = "aqsgd"            # fp32 | directq | aqsgd
    fw_bits: int = 4               # forward activation bits
    bw_bits: int = 8               # backward activation-gradient bits
    buffer_bits: int = 0           # 0 = raw buffer; else z-bit stored (§H.5)
    buffer_dtype: str = "float32"  # raw-buffer storage dtype
    stochastic: bool = True
    backend: str = "auto"          # boundary op: reference | pallas | auto

    def with_(self, **kw):
        return dataclasses.replace(self, **kw)

    @property
    def compresses(self) -> bool:
        return self.mode != "fp32"

    def fw_wire_bytes(self, shape) -> int:
        if not self.compresses:
            return int(np.prod(shape)) * 4
        return Q.wire_bytes(shape, self.fw_bits)

    def bw_wire_bytes(self, shape) -> int:
        if not self.compresses:
            return int(np.prod(shape)) * 4
        return Q.wire_bytes(shape, self.bw_bits)


# ---------------------------------------------------------------------------
# message buffers
# ---------------------------------------------------------------------------

def init_buffers(cc: CompressionConfig, num_boundaries: int,
                 num_samples: int, seq: int, d: int) -> Optional[dict]:
    """Buffers for the whole dataset (AQ-SGD only)."""
    if cc.mode != "aqsgd":
        return None
    nb = num_boundaries
    bufs = {"seen": jnp.zeros((nb, num_samples), bool)}
    if cc.buffer_bits:
        pw = Q.packed_width(d, cc.buffer_bits)
        bufs["codes"] = jnp.zeros((nb, num_samples, seq, pw), jnp.uint8)
        bufs["scale"] = jnp.ones((nb, num_samples, seq, 1), jnp.float32)
    else:
        bufs["m"] = jnp.zeros((nb, num_samples, seq, d),
                              jnp.dtype(cc.buffer_dtype))
    return bufs


def buffer_nbytes(cc: CompressionConfig, num_boundaries: int,
                  num_samples: int, seq: int, d: int) -> int:
    """Storage cost of the message buffers (paper §3.3 / §G)."""
    if cc.mode != "aqsgd":
        return 0
    nb = num_boundaries
    if cc.buffer_bits:
        return nb * num_samples * seq * (Q.packed_width(d, cc.buffer_bits)
                                         + 4)
    return nb * num_samples * seq * d * jnp.dtype(cc.buffer_dtype).itemsize


def _rows(x: jax.Array, sample_ids: jax.Array) -> jax.Array:
    """``x[:, sample_ids]``, one dynamic slice a sample, so that only
    those rows are read: on the TPU, XLA lowers that gather to slices
    of the whole store."""
    return jnp.concatenate([
        lax.dynamic_slice_in_dim(x, sample_ids[j], 1, axis=1)
        for j in range(sample_ids.shape[0])], axis=1)


def _set_rows(x: jax.Array, sample_ids: jax.Array,
              rows: jax.Array) -> jax.Array:
    """``x.at[:, sample_ids].set(rows)``, one dynamic update a sample,
    each in place and in the store's own layout."""
    rows = rows.astype(x.dtype)
    for j in range(sample_ids.shape[0]):
        x = lax.dynamic_update_slice_in_dim(x, rows[:, j:j + 1],
                                            sample_ids[j], axis=1)
    return x


@jax.named_scope(tracing.STORE)
def read_buffers(cc: CompressionConfig, bufs: dict, sample_ids: jax.Array,
                 d: int) -> tuple:
    """-> (m (nb, B, S, d) float32, seen (nb, B)): every boundary's
    messages and first-visit flags for the given samples."""
    if cc.buffer_bits:
        m = B.decode(_rows(bufs["codes"], sample_ids),
                     _rows(bufs["scale"], sample_ids),
                     bits=cc.buffer_bits, d=d, backend=cc.backend)
    else:
        m = _rows(bufs["m"], sample_ids).astype(jnp.float32)
    return m, _rows(bufs["seen"], sample_ids)


@jax.named_scope(tracing.STORE)
def write_buffers(cc: CompressionConfig, bufs: dict, sample_ids: jax.Array,
                  m_new: jax.Array) -> dict:
    """Store the updated messages m_new (nb, B, S, d) for `sample_ids`
    at every boundary (raw dtype, or z-bit codes + scales when
    ``cc.buffer_bits``) and mark them seen — the write half of
    Algorithm 2's buffer state."""
    bufs = dict(bufs)
    if cc.buffer_bits:
        packed, scale = B.encode(m_new, bits=cc.buffer_bits,
                                 stochastic=False, backend=cc.backend)
        bufs["codes"] = _set_rows(bufs["codes"], sample_ids, packed)
        bufs["scale"] = _set_rows(bufs["scale"], sample_ids, scale)
    else:
        bufs["m"] = _set_rows(bufs["m"], sample_ids, m_new)
    bufs["seen"] = _set_rows(bufs["seen"], sample_ids,
                             jnp.ones(m_new.shape[:2], bool))
    return bufs


# ---------------------------------------------------------------------------
# the boundary op (forward substitution + quantized backward gradient)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make_ste(bw_bits: int, stochastic: bool, backend: str):
    """Straight-through boundary: forward value = message m, backward
    gradient = Q_bw(∇) (the paper quantizes the backward activation
    gradient directly — Algorithm 1 line 11).  The quantize→pack→unpack
    round trip runs inside this custom_vjp, so on the pallas backend the
    backward wire codec is fused too."""

    @jax.custom_vjp
    def ste(h, m_used, key):
        del h, key
        return m_used

    def fwd(h, m_used, key):
        del h
        return m_used, key

    def bwd(key, g):
        if bw_bits >= 32:
            gq = g
        else:
            gq = B.roundtrip(g, bits=bw_bits, stochastic=stochastic,
                             key=key, backend=backend)
        return (gq, jnp.zeros_like(g),
                np.zeros(key.shape, jax.dtypes.float0))

    ste.defvjp(fwd, bwd)
    return ste


@jax.named_scope(tracing.BOUNDARY)
def apply_boundary(cc: CompressionConfig, h: jax.Array, key: jax.Array,
                   m: Optional[jax.Array] = None,
                   seen: Optional[jax.Array] = None,
                   quantize_bw: bool = True):
    """One pipeline-boundary crossing.

    h: (B, S, d) activations leaving machine a (differentiable).
    m: (B, S, d) previous messages for these samples (aqsgd only).
    seen: (B,) first-visit mask.

    Returns (h_out, m_new):
      h_out — what machine b computes on (forward = message, backward =
              Q_bw(gradient) via the straight-through custom_vjp);
      m_new — updated messages to persist (None unless aqsgd).
    """
    kf, kb = jax.random.split(key)
    dtype = h.dtype
    backend = B.resolve_backend(cc.backend)
    h_sg = jax.lax.stop_gradient(h).astype(jnp.float32)

    if cc.mode == "fp32":
        return h, None
    if cc.mode == "directq":
        m_used = B.roundtrip(h_sg, bits=cc.fw_bits,
                             stochastic=cc.stochastic, key=kf,
                             backend=backend)
        m_new = None
    elif cc.mode == "aqsgd":
        assert m is not None and seen is not None
        _, _, m_upd = B.encode_delta(h_sg, m, bits=cc.fw_bits,
                                     stochastic=cc.stochastic, key=kf,
                                     backend=backend)
        m_used = jnp.where(seen[:, None, None], m_upd, h_sg)
        m_new = m_used
    else:
        raise ValueError(cc.mode)

    bw_bits = cc.bw_bits if quantize_bw else 32
    ste = _make_ste(bw_bits, cc.stochastic, backend)
    h_out = ste(h, m_used.astype(dtype), kb)
    return h_out, m_new
