"""The bit-exactness contract between the reference and Pallas boundary
backends.

Both backends of `repro.core.boundary` must produce IDENTICAL bits —
wire codes, scales, updated messages m_new, and backward gradients —
under jit (the only regime the pipeline ever runs; XLA strength-reduces
constant divisions under jit, so eager reference output may differ by 1
ulp and is not part of the contract).  This is what lets the fused
Pallas kernels replace the jnp chain without changing the trained
model, and what keeps sender/receiver buffer replicas synchronized
across machines running either backend (Algorithm 2).

Sweeps: bits ∈ {2, 4, 8} × {deterministic, stochastic} × {f32, bf16}
buffers × row counts that are odd / ragged vs the kernel block size.

Scope: the contract is per-op — same inputs, same bits.  End-to-end
training trajectories may drift at ulp level across backends because
the opaque pallas_call changes XLA's fusion of SURROUNDING model ops
(verified: boundary outputs bit-equal, stage-interior activations 1-ulp
apart) — that is compiler noise, not a codec divergence, and it is why
these tests pin the boundary ops rather than whole-model runs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aqsgd
from repro.core import boundary as B
from repro.core.aqsgd import CompressionConfig

BITS = [2, 4, 8]
KEY = jax.random.PRNGKey(0)


def _data(r, d, dtype, scale=0.1):
    a = jax.random.normal(jax.random.PRNGKey(1), (r, d),
                          jnp.float32).astype(dtype)
    m = (scale * jax.random.normal(jax.random.PRNGKey(2), (r, d))
         ).astype(dtype)
    return a, m


@functools.partial(jax.jit, static_argnames=("bits", "stoch", "backend"))
def _enc(a, m, key, *, bits, stoch, backend):
    return B.encode_delta(a, m, bits=bits, stochastic=stoch, key=key,
                          backend=backend)


@functools.partial(jax.jit, static_argnames=("bits", "backend"))
def _dec(packed, scale, m, *, bits, backend):
    return B.decode_accumulate(packed, scale, m, bits=bits,
                               backend=backend)


@functools.partial(jax.jit, static_argnames=("bits", "stoch", "backend"))
def _rt(x, key, *, bits, stoch, backend):
    return B.roundtrip(x, bits=bits, stochastic=stoch, key=key,
                       backend=backend)


def _eq(name, a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                  err_msg=name)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("r", [8, 37, 200])
def test_encode_delta_bit_identical(bits, stoch, dtype, r):
    """Forward wire: packed codes, scales, and m_new all bit-equal."""
    a, m = _data(r, 256, dtype)
    ref = _enc(a, m, KEY, bits=bits, stoch=stoch, backend="reference")
    pal = _enc(a, m, KEY, bits=bits, stoch=stoch, backend="pallas")
    for name, x, y in zip(("packed", "scale", "m_new"), ref, pal):
        _eq(name, x, y)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_accumulate_bit_identical(bits, dtype):
    """Receiver side, and the Algorithm-2 invariant across backends:
    sender m_new == receiver reconstruction, whichever backend ran
    either side."""
    a, m = _data(37, 256, dtype)
    packed, scale, m_new = _enc(a, m, KEY, bits=bits, stoch=False,
                                backend="reference")
    ref = _dec(packed, scale, m, bits=bits, backend="reference")
    pal = _dec(packed, scale, m, bits=bits, backend="pallas")
    _eq("decode", ref, pal)
    _eq("sender-vs-receiver", m_new, pal)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
def test_roundtrip_bit_identical(bits, stoch):
    """The DirectQ / backward-gradient wire round trip."""
    x, _ = _data(200, 256, jnp.float32)
    _eq("roundtrip",
        _rt(x, KEY, bits=bits, stoch=stoch, backend="reference"),
        _rt(x, KEY, bits=bits, stoch=stoch, backend="pallas"))


@pytest.mark.parametrize("mode", ["aqsgd", "directq"])
@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stoch", [False, True])
def test_apply_boundary_forward_and_backward_grads(mode, bits, stoch):
    """The full boundary op, gradients included: the custom_vjp routes
    the backward-gradient quantize/pack through the selected backend and
    both backends must agree bit-for-bit."""
    h = jax.random.normal(jax.random.PRNGKey(4), (4, 7, 256))
    m = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (4, 7, 256))
    seen = jnp.array([True, True, False, True])

    @functools.partial(jax.jit, static_argnames=("backend",))
    def run(h, m, seen, key, *, backend):
        cc = CompressionConfig(mode=mode, fw_bits=bits, bw_bits=bits,
                               stochastic=stoch, backend=backend)

        def loss(h):
            out, m_new = aqsgd.apply_boundary(cc, h, key, m, seen)
            return jnp.sum(out ** 3), m_new

        (l, m_new), g = jax.value_and_grad(loss, has_aux=True)(h)
        return l, m_new, g

    l_r, m_r, g_r = run(h, m, seen, KEY, backend="reference")
    l_p, m_p, g_p = run(h, m, seen, KEY, backend="pallas")
    _eq("loss", l_r, l_p)
    _eq("grad", g_r, g_p)
    if mode == "aqsgd":
        _eq("m_new", m_r, m_p)


@pytest.mark.parametrize("buffer_bits", BITS)
def test_buffer_codec_bit_identical(buffer_bits):
    """z-bit stored messages (§H.5): the fused quantize_pack /
    unpack_dequant kernels must reproduce the reference buffer codec
    exactly through a write→read cycle."""
    ids = jnp.array([3, 7], jnp.int32)
    m = jax.random.normal(KEY, (2, 2, 8, 128))

    @functools.partial(jax.jit, static_argnames=("backend",))
    def cycle(m, *, backend):
        cc = CompressionConfig(mode="aqsgd", buffer_bits=buffer_bits,
                               backend=backend)
        bufs = aqsgd.init_buffers(cc, 2, 10, 8, 128)
        bufs = aqsgd.write_buffers(cc, bufs, ids, m)
        return bufs["codes"], bufs["scale"], \
            aqsgd.read_buffers(cc, bufs, ids, 128)[0]

    c_r, s_r, out_r = cycle(m, backend="reference")
    c_p, s_p, out_p = cycle(m, backend="pallas")
    _eq("codes", c_r, c_p)
    _eq("scale", s_r, s_p)
    _eq("read", out_r, out_p)


def test_pipeline_has_no_unfused_boundary_calls():
    """Every wire-path quantize/pack must route through core.boundary
    — never the unfused Q.quantize→Q.pack_codes chain (that chain
    costs ~6 HBM round-trips per crossing).  The assertion lives in
    the `no-unfused-quantize` lint rule (repro.analysis), which covers
    training/pipeline.py alias-proof; this is its one-line test
    invocation."""
    from repro.analysis import run_rule

    assert run_rule("no-unfused-quantize") == []
