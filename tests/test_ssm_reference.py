"""The benchmark's plain Mamba-2 reference (`bench/reference/ssm.py`)
against the program, and its quadratic SSD against a token-by-token
recurrence.  Smoke widths on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)
from harness import sim_train  # noqa: E402
from reference import ssm as ref  # noqa: E402
from test_ssm_cell import smoke_cell  # noqa: E402

from repro.models import model as Mo  # noqa: E402


@pytest.mark.parametrize("block", [16, 256])
def test_reference_loss_and_gradients_match_the_program(block,
                                                        monkeypatch):
    """The same weights (the reference's, in the program's layout) and
    batch on both sides, every matmul in full float32.  The program runs
    the chunked SSD and the reference the quadratic form in blocks of
    ``block`` query rows (16: six blocks and the path over earlier keys;
    256: one), so the two sum the same terms in another order: float32
    rounding, about 1e-6 relative here (4.5e-6 at worst, the A_log
    leaf).  The tolerances leave ten times that; a bfloat16 pass, with
    its 2^-8 rounding, would miss them by far."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", block)
    c = smoke_cell().config
    mcfg = sim_train.model_config(c)
    params = ref.init_params(c, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda: Mo.init_params(mcfg,
                                                 jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    b, s = 2, 96
    tok = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0,
                             c["vocab_size"])
    batch = {"tokens": tok[:, :-1], "targets": tok[:, 1:],
             "mask": jnp.ones((b, s), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        (l_ref, _), g_ref = jax.value_and_grad(
            lambda p: ref.loss(c, p, batch, 4), has_aux=True)(params)
        (l_prog, _), g_prog = jax.value_and_grad(
            lambda p: Mo.loss_fn(p, mcfg, batch), has_aux=True)(params)
    assert float(l_prog) == pytest.approx(float(l_ref), rel=1e-5)
    for (path, a), g in zip(jax.tree_util.tree_leaves_with_path(g_ref),
                            jax.tree.leaves(g_prog)):
        gap = float(jnp.linalg.norm(g - a) / jnp.linalg.norm(a))
        assert gap < 5e-5, (jax.tree_util.keystr(path), gap)


def _recurrence(x, dt, A, B, C):
    """S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t B_tᵀ, y_t = S_t C_t, token by
    token in float64."""
    x, dt, A, B, C = (np.asarray(a, np.float64) for a in (x, dt, A, B, C))
    st = np.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:])
    ys = []
    for t in range(x.shape[1]):
        st = (st * np.exp(dt[:, t] * A)[..., None, None]
              + np.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t], B[:, t]))
        ys.append(np.einsum("bhpn,bn->bhp", st, C[:, t]))
    return np.stack(ys, 1)


def test_quadratic_ssd_equals_the_token_recurrence():
    """At seq 1024 in blocks of 256, A from 1 to 16 over the heads, Δ
    near 1 over the first half and near 0.01 over the second: there the
    cumulative sum of Δ·A reaches 8,192 while a token's decay is
    exp(-0.01 A), so an exponent taken as the difference of two
    cumulative sums loses 2^-10 in float32 and errs by 2e-5 to 8e-5 of
    the largest output.  Summed within the block, the reference errs by
    1e-7 (float32 rounding); 1e-6 is the tolerance."""
    rng = np.random.default_rng(0)
    b, s, h, p, n = 2, 1024, 8, 4, 8
    x = rng.normal(size=(b, s, h, p)).astype(np.float32)
    dt = rng.uniform(0.8, 1.2, size=(b, s, h)).astype(np.float32)
    dt[:, s // 2:] *= 0.01
    A = -np.linspace(1.0, 16.0, h).astype(np.float32)
    B = rng.normal(size=(b, s, n)).astype(np.float32)
    C = rng.normal(size=(b, s, n)).astype(np.float32)
    want = _recurrence(x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.ssd(*map(jnp.asarray, (x, dt, A, B, C)), 256))
    err = (np.max(np.abs(got - want), axis=(0, 1, 3))
           / np.max(np.abs(want), axis=(0, 1, 3)))
    assert np.all(err < 1e-6), err
