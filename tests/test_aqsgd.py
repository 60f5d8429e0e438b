"""Tests for the AQ-SGD core: boundary semantics, buffer codec, gradient
quantization, and the paper's headline qualitative claim (AQ-SGD tracks
FP32 where DirectQ degrades, at aggressive bit widths)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig
from repro.configs.base import get_config
from repro.core import aqsgd
from repro.core import quantization as Q
from repro.core.aqsgd import CompressionConfig
from repro.data.pipeline import Dataset, DatasetConfig
from repro.optim.adamw import AdamWConfig
from repro.training import simulated as sim

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# boundary op semantics
# ---------------------------------------------------------------------------

def test_first_visit_sends_full_precision():
    cc = CompressionConfig(mode="aqsgd", fw_bits=2)
    h = jax.random.normal(KEY, (4, 8, 16))
    m = jnp.zeros_like(h)
    seen = jnp.zeros((4,), bool)
    h_out, m_new = aqsgd.apply_boundary(cc, h, KEY, m, seen)
    np.testing.assert_allclose(np.asarray(h_out), np.asarray(h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m_new), np.asarray(h), atol=1e-6)


def test_revisit_sends_quantized_delta():
    cc = CompressionConfig(mode="aqsgd", fw_bits=4, stochastic=False)
    h = jax.random.normal(KEY, (4, 8, 16))
    m = h + 0.01 * jax.random.normal(jax.random.PRNGKey(1), h.shape)
    seen = jnp.ones((4,), bool)
    h_out, m_new = aqsgd.apply_boundary(cc, h, KEY, m, seen)
    expect = m + Q.qdq(h - m, 4, stochastic=False)
    np.testing.assert_allclose(np.asarray(h_out), np.asarray(expect),
                               atol=1e-6)
    # self-reinforcing property: message error shrinks vs direct quant
    err_aq = float(jnp.linalg.norm(h - m_new))
    err_dq = float(jnp.linalg.norm(h - Q.qdq(h, 4, stochastic=False)))
    assert err_aq < err_dq


def test_backward_gradient_is_quantized():
    cc = CompressionConfig(mode="directq", fw_bits=8, bw_bits=2,
                           stochastic=False)

    def f(h):
        out, _ = aqsgd.apply_boundary(cc, h, KEY)
        return jnp.sum(out ** 3)

    h = jax.random.normal(KEY, (2, 4, 8))
    g = jax.grad(f)(h)
    out, _ = aqsgd.apply_boundary(cc, h, KEY)
    true_g = 3.0 * out ** 2                     # upstream gradient at m
    # bwd applies qdq(true_g) with bw_bits and the bwd sub-key
    _, kb = jax.random.split(KEY)
    expect = Q.qdq(true_g, 2, stochastic=False)
    np.testing.assert_allclose(np.asarray(g), np.asarray(expect), atol=1e-5)
    # 2-bit quantization must actually have changed something
    assert float(jnp.max(jnp.abs(g - true_g))) > 1e-3


def test_non_byte_aligned_bits_still_supported():
    """The paper's fw3/bw6 ablation widths (not densely packable) must
    keep working through the boundary op — they route to the reference
    chain with raw u8 codes and match the fake-quant semantics."""
    cc = CompressionConfig(mode="aqsgd", fw_bits=3, bw_bits=6,
                           stochastic=False)
    h = jax.random.normal(KEY, (4, 8, 16))
    m = h + 0.01 * jax.random.normal(jax.random.PRNGKey(1), h.shape)
    seen = jnp.ones((4,), bool)
    h_out, m_new = aqsgd.apply_boundary(cc, h, KEY, m, seen)
    expect = m + Q.qdq(h - m, 3, stochastic=False)
    np.testing.assert_allclose(np.asarray(h_out), np.asarray(expect),
                               atol=1e-6)
    g = jax.grad(lambda x: jnp.sum(
        aqsgd.apply_boundary(cc, x, KEY, m, seen)[0] ** 2))(h)
    assert np.isfinite(np.asarray(g)).all()


def test_fp32_mode_is_identity_with_gradient():
    cc = CompressionConfig(mode="fp32")
    h = jax.random.normal(KEY, (2, 4, 8))
    out, m_new = aqsgd.apply_boundary(cc, h, KEY)
    assert m_new is None
    np.testing.assert_array_equal(np.asarray(out), np.asarray(h))
    g = jax.grad(lambda x: jnp.sum(aqsgd.apply_boundary(cc, x, KEY)[0] ** 2)
                 )(h)
    np.testing.assert_allclose(np.asarray(g), np.asarray(2 * h), atol=1e-6)


# ---------------------------------------------------------------------------
# buffer codec (fp and z-bit storage, §H.5)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("buffer_bits", [0, 8, 4])
def test_buffer_roundtrip(buffer_bits):
    cc = CompressionConfig(mode="aqsgd", buffer_bits=buffer_bits)
    bufs = aqsgd.init_buffers(cc, 2, 10, 8, 16)
    ids = jnp.array([3, 7], jnp.int32)
    m = jax.random.normal(KEY, (2, 2, 8, 16))
    bufs = aqsgd.write_buffers(cc, bufs, ids, m)
    got, seen = aqsgd.read_buffers(cc, bufs, ids, 16)
    tol = 1e-6 if buffer_bits == 0 else \
        float(jnp.max(jnp.abs(m))) * 2.0 / ((1 << buffer_bits) - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(m), atol=tol)
    assert np.asarray(seen).all()
    assert bool(bufs["seen"][1, 3]) and bool(bufs["seen"][0, 7])
    assert not bool(bufs["seen"][0, 4])
    # the other samples' rows are left as they were
    _, rest = aqsgd.read_buffers(cc, bufs, jnp.array([0, 9], jnp.int32), 16)
    assert not np.asarray(rest).any()


def test_buffer_nbytes_matches_paper_scale():
    """GPT2-XL example from §3.3: buffers for the full corpus are ~1 TB
    in fp32 when the boundary tensor is seq 1024 × d 1600 over 7
    boundaries and a WikiText2-scale corpus (~2M tokens / 1024)."""
    cc = CompressionConfig(mode="aqsgd")
    n_samples = 2_000_000 // 1024
    b = aqsgd.buffer_nbytes(cc, 7, n_samples, 1024, 1600)
    assert 50e9 < b < 200e9   # per-boundary-pair copy; x2 sides + opt state
    # and z-bit storage cuts it ~8x (4-bit + scales)
    cc4 = cc.with_(buffer_bits=4)
    assert aqsgd.buffer_nbytes(cc4, 7, n_samples, 1024, 1600) < b / 6


# ---------------------------------------------------------------------------
# simulated trainer end-to-end semantics
# ---------------------------------------------------------------------------

def _mini_setup(mode, fw_bits=2, bw_bits=4, steps=30, stages=4, lr=2e-3,
                dp_grad_bits=0, dp_workers=1, buffer_bits=0,
                initial_params=None):
    mcfg = get_config("gpt2-xl-paper", smoke=True).with_(num_layers=4)
    dc = DatasetConfig(num_samples=32, seq_len=32, vocab_size=512, seed=3)
    ds = Dataset(dc)
    tcfg = sim.SimTrainConfig(
        num_stages=stages,
        comm=CommConfig.from_legacy(
            CompressionConfig(mode=mode, fw_bits=fw_bits,
                              bw_bits=bw_bits, buffer_bits=buffer_bits),
            dp_grad_bits=dp_grad_bits),
        optimizer=AdamWConfig(lr=lr, warmup_steps=5, total_steps=steps,
                              schedule="constant"),
        dp_workers=dp_workers)
    state, losses = sim.train(mcfg, tcfg, ds, num_steps=steps, batch_size=8,
                              key=jax.random.PRNGKey(0),
                              initial_params=initial_params)
    return state, losses


@pytest.mark.slow
def test_fp32_pipeline_matches_no_pipeline():
    """K-stage fp32 simulation must equal monolithic training exactly."""
    _, l4 = _mini_setup("fp32", steps=6, stages=4)
    _, l1 = _mini_setup("fp32", steps=6, stages=1)
    np.testing.assert_allclose(l4, l1, rtol=1e-5)


@pytest.mark.slow
def test_paper_claim_aqsgd_tracks_fp32_directq_degrades():
    """Fig. 1a / Fig. 3: *fine-tuning* (the paper's setting) at fw2 bw4 —
    AQ-SGD stays close to FP32 while DirectQ is clearly worse."""
    # phase 1: pre-train a base model in fp32 (the "foundation model")
    base_state, base_losses = _mini_setup("fp32", steps=80, lr=2e-3)
    base = base_state["params"]
    assert np.mean(base_losses[-5:]) < 2.5       # learned the structure
    # phase 2: fine-tune at low lr with each compression mode
    steps = 40
    _, l_fp = _mini_setup("fp32", steps=steps, lr=3e-4,
                          initial_params=base)
    _, l_aq = _mini_setup("aqsgd", steps=steps, lr=3e-4,
                          initial_params=base)
    _, l_dq = _mini_setup("directq", steps=steps, lr=3e-4,
                          initial_params=base)
    tail = slice(-8, None)
    fp, aq, dq = (float(np.mean(l[tail])) for l in (l_fp, l_aq, l_dq))
    assert aq < dq, (fp, aq, dq)
    assert abs(aq - fp) < 0.5 * abs(dq - fp) + 1e-6, (fp, aq, dq)


@pytest.mark.slow
def test_low_precision_buffer_still_converges():
    """§H.5: 4-bit previous-message storage remains usable."""
    _, l = _mini_setup("aqsgd", steps=25, buffer_bits=4)
    assert np.isfinite(l).all()
    assert np.mean(l[-5:]) < np.mean(l[:5])


@pytest.mark.slow
def test_dp_gradient_compression_combo():
    """Fig. 5: AQ-SGD + error-feedback DP gradient compression trains."""
    _, l = _mini_setup("aqsgd", steps=20, dp_grad_bits=4, dp_workers=2)
    assert np.isfinite(l).all()
    assert np.mean(l[-5:]) < np.mean(l[:5])
