"""The benchmark cell ``mamba2-1chip.aqsgd`` run whole at smoke widths
on the CPU (`bench/harness/sim_train.py::run`): ``correct`` for the
program, and not for a step that keeps its state, for half of each
batch masked out, for an AQ-SGD sender that keeps the previous message,
or for the program's bfloat16 path (the control).  The cell's limits
are the chip's, unchanged."""
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import calibrate  # noqa: E402
from harness import compare, device, sim_train, spec  # noqa: E402

CELL = "mamba2-1chip.aqsgd"
SEED = 2 ** 31 + 99
# widths a CPU test holds; 4 layers for the cell's 4 stages, two
# chunks of 32 in the program's SSD over the sequence of 64
SMOKE = dict(num_layers=4, d_model=64, ssm_state=16, ssm_headdim=16,
             ssm_chunk=32, vocab_size=512)


def smoke_cell():
    cell = spec.resolve(spec.load_spec(), CELL)
    return dataclasses.replace(cell, config=dict(cell.config, **SMOKE),
                               traffic=dict(cell.traffic, seq=64))


@pytest.fixture
def cpu_peaks(monkeypatch):
    """A peak table entry for the CPU, which `peaks.json` rightly
    lacks."""
    monkeypatch.setattr(device, "peaks", lambda kind, path=None: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _run():
    return sim_train.run(smoke_cell(), seed=SEED, seconds=0.2,
                         trace=False, t_start=time.perf_counter(),
                         devices=jax.devices()[:1], log=lambda *a: None)


def _unchanged(real):
    def step(state, batch, key, **kw):
        # the real step donates what it is handed: hand it a copy
        return state, real(jax.tree.map(jnp.copy, state), batch, key,
                           **kw)[1]
    return step


def _half_batch(real):
    def step(state, batch, key, **kw):
        m = batch["mask"]
        return real(state, dict(batch, mask=m.at[m.shape[0] // 2:]
                                .set(0.0)), key, **kw)
    return step


def test_program_is_correct(cpu_peaks):
    res = _run()
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault, monkeypatch, cpu_peaks):
    from repro.training import simulated as sim
    wrap = {"unchanged": _unchanged, "half_batch": _half_batch}[fault]
    monkeypatch.setattr(sim, "train_step", wrap(sim.train_step))
    res = _run()
    assert res["correct"] is False
    if fault == "unchanged":
        # 1 up to the rounding of the weights made again from the seed
        assert res["checks"]["change_gap"]["value"] == pytest.approx(
            1.0, abs=1e-4)


def test_sender_that_keeps_the_previous_message(cpu_peaks):
    with calibrate.stale_delta():
        res = _run()
    assert res["correct"] is False
    assert res["checks"]["delta_gap"]["value"] == pytest.approx(1.0,
                                                                abs=1e-6)


def test_control_in_bfloat16_is_not_correct():
    c = smoke_cell()
    (_, _, vals), = calibrate.readings(c, SEED, ["bf16"], lambda *a: None)
    ok, checks = compare.judge(vals, c.own["limits"])
    assert not ok, checks
    share = checks["bf16_share_gap"]
    assert share["value"] > share["limit"], checks
