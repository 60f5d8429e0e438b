"""``correct`` comes out false when the timed path is broken underneath,
and for the control, at a size the CPU holds.  The rest of a run is
driven as on the chip; only the look for a chip is skipped."""
import time

import jax
import numpy as np
import pytest

from conftest import smoke_cell
from harness import compare, sim_train, spec

SEED = 2 ** 31 + 99
CELLS = [w["name"] for w in spec.load_spec()["workloads"]]
AQSGD_CELLS = [c for c in CELLS
               if spec.resolve(spec.load_spec(), c).traffic["mode"]
               == "aqsgd"]


def _run(cell):
    return sim_train.run(cell, seed=SEED, seconds=0.5, trace=False,
                         t_start=time.perf_counter(),
                         devices=jax.devices()[:1], log=lambda *a: None)


@pytest.fixture
def real_step():
    from repro.training import simulated as sim
    return sim.train_step


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(cell, monkeypatch,
                                               real_step, cpu_peaks):
    from repro.training import simulated as sim

    def unchanged(state, batch, key, **kw):
        return state, real_step(state, batch, key, **kw)[1]
    monkeypatch.setattr(sim, "train_step", unchanged)
    res = _run(smoke_cell(cell))
    assert res["correct"] is False
    # 1 up to the rounding of the weights made again from the seed
    assert res["checks"]["change_gap"]["value"] == pytest.approx(
        1.0, abs=1e-4)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(cell, monkeypatch, real_step,
                                    cpu_peaks):
    from repro.training import simulated as sim

    def half(state, batch, key, **kw):
        m = batch["mask"]
        return real_step(state, dict(batch, mask=m.at[m.shape[0] // 2:]
                                     .set(0.0)), key, **kw)
    monkeypatch.setattr(sim, "train_step", half)
    res = _run(smoke_cell(cell))
    assert res["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_is_not_correct(cell):
    import calibrate
    c = smoke_cell(cell)
    (_, _, vals), = calibrate.readings(c, SEED, ["bf16"], lambda *a: None)
    ok, checks = compare.judge(vals, c.own["limits"])
    assert not ok, checks


@pytest.mark.parametrize("cell", AQSGD_CELLS)
def test_sender_that_keeps_the_previous_message(cell, cpu_peaks):
    """The AQ-SGD sender's update dropped: the revisiting step stores
    the first epoch's messages, so ``delta_gap`` reads 1."""
    import calibrate
    with calibrate.stale_delta():
        res = _run(smoke_cell(cell))
    assert res["correct"] is False
    assert res["checks"]["delta_gap"]["value"] == pytest.approx(1.0,
                                                                abs=1e-6)


def test_delta_gap_reads_the_share_of_the_change_taken():
    rng = np.random.default_rng(0)
    prev = [rng.normal(size=(4, 64, 32)) for _ in range(3)]
    d = [rng.normal(size=p.shape) * 0.2 for p in prev]
    new = [p + x for p, x in zip(prev, d)]
    noisy = [p + x + rng.normal(size=p.shape) * 0.05
             for p, x in zip(prev, d)]
    assert compare.delta_gap(new, prev, new, prev) == pytest.approx(0.0)
    assert compare.delta_gap(prev, prev, new, prev) == pytest.approx(1.0)
    half = [p + 0.5 * x for p, x in zip(prev, d)]
    assert compare.delta_gap(half, prev, new, prev) == pytest.approx(0.5)
    # unbiased noise averages out of the share
    assert compare.delta_gap(noisy, prev, new, prev) < 0.05
