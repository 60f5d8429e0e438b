"""Fault injection + guarded recovery gates (ISSUE 8 planes 2 and 3).

Fast tier: the `FaultPlan` grammar, the internal fault-wrapper wires
(registered but HIDDEN from enumeration), corruption semantics, the
in-graph `guard_dp_pair` bit-exactness contract, host-side
`check_train_state` attribution on synthetic states, and the serving
batcher's slot-level isolation (poisoned request evicted to
DONE(error), surviving slots' token streams bit-identical).

Slow tier: the headline ISSUE-8 gates end-to-end through
`launch.runner` — kill-and-resume bit-parity for every compressed DP
wire {psum, ring, ring-sharded} with EF + activation compression on,
fault -> detect (named plane/wire/step) -> recover-from-checkpoint
bit-parity per plane, and the real CLI `--kill-at` (exit 17) /
`--resume` path in subprocesses.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommConfig, faults as F, wires as W
from repro.data.pipeline import Dataset, DatasetConfig
from workerproc import ROOT, loss_lines


# ---------------------------------------------------------------------------
# FaultPlan grammar
# ---------------------------------------------------------------------------

def test_plan_parse_roundtrip():
    plan = F.FaultPlan.parse("3:dp:nan-scale, 5:fw:drop-hop")
    assert plan.text() == "3:dp:nan-scale,5:fw:drop-hop"
    assert bool(plan)
    assert [s.kind for s in plan.at(3)] == ["nan-scale"]
    assert plan.at(3, "fw") == []
    assert plan.at(4) == []
    assert not F.FaultPlan.parse("")
    assert F.FaultPlan.parse("") == F.FaultPlan()


def test_plan_parse_errors():
    with pytest.raises(ValueError, match="step:plane:kind"):
        F.FaultPlan.parse("3:dp")
    with pytest.raises(ValueError, match="unknown fault plane"):
        F.FaultPlan.parse("3:qq:nan-scale")
    with pytest.raises(ValueError, match="unknown fault kind"):
        F.FaultPlan.parse("3:dp:meteor")
    # all-zero payloads are legitimate on bw/kv: drop-hop rejected
    with pytest.raises(ValueError, match="not injectable"):
        F.FaultPlan.parse("3:kv:drop-hop")
    with pytest.raises(ValueError, match="< 0"):
        F.FaultPlan.parse("-1:dp:nan-scale")


# ---------------------------------------------------------------------------
# internal fault-wrapper wires (registry pattern, hidden from enumeration)
# ---------------------------------------------------------------------------

def test_fault_wire_registered_but_hidden():
    name = F.fault_wire("ring", "nan-scale")
    assert name == "ring+fault-nan-scale"
    assert name == F.fault_wire("ring", "nan-scale")   # idempotent
    spec = W.get_wire(name)                            # resolvable
    assert spec.internal and spec.plane == "dp-grad"
    assert spec.chunkable == W.get_wire("ring").chunkable
    # enumeration (CLI choices, --list-wires, registry-completeness
    # gates in test_comm/test_hlo_cost) never sees internal wires
    assert name not in W.wire_names("dp-grad")
    assert name in W.wire_names("dp-grad", include_internal=True)
    assert all(not s.internal for s in W.list_wires())


def test_faulted_comm_swaps_wire():
    comm = CommConfig.from_dict({"dp": {"bits": 4, "wire": "ring"}})
    spec = F.FaultSpec(3, "dp", "corrupt-codes")
    fc = F.faulted_comm(comm, spec)
    assert fc.dp.wire == "ring+fault-corrupt-codes"
    assert comm.dp.wire == "ring"
    with pytest.raises(ValueError, match="dp.bits"):
        F.faulted_comm(CommConfig.from_dict({}), spec)


# ---------------------------------------------------------------------------
# corruption semantics + in-graph guard
# ---------------------------------------------------------------------------

def test_corrupt_array_kinds():
    x = jnp.ones((2, 3), jnp.float32)
    cc = np.asarray(F.corrupt_array(x, "corrupt-codes"))
    assert np.abs(cc).max() > F.GUARD_MAX and np.isfinite(cc).all()
    assert np.isnan(np.asarray(F.corrupt_array(x, "nan-scale"))).all()
    assert not np.asarray(F.corrupt_array(x, "drop-hop")).any()
    # bf16 (ml_dtypes, numpy kind 'V') is corrupted too
    b = F.corrupt_array(jnp.ones((4,), jnp.bfloat16), "nan-scale")
    assert np.isnan(np.asarray(b).astype(np.float32)).all()
    # ints/bools pass through unchanged (codes corruption is modeled
    # post-decode on the float payload)
    i = jnp.arange(4)
    assert F.corrupt_array(i, "nan-scale") is i


def test_guard_dp_pair_clean_passthrough_bit_exact():
    g = {"a": jnp.asarray([1.5, -2.25]), "b": jnp.asarray([[3e20]])}
    e = jnp.asarray([0.125, 7.0])
    og, oe = jax.jit(F.guard_dp_pair)(g, e)
    for a, b in zip(jax.tree_util.tree_leaves((g, e)),
                    jax.tree_util.tree_leaves((og, oe))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("bad", [
    jnp.asarray([1.0, np.nan]),            # non-finite
    jnp.asarray([1.0, 5e31]),              # above GUARD_MAX
    jnp.asarray([0.0, 0.0]),               # all-zero (dropped hop)
])
def test_guard_dp_pair_poisons_mean_and_carry(bad):
    e = jnp.asarray([0.5, 0.5])
    og, oe = F.guard_dp_pair({"g": bad}, {"g": e})
    assert np.isnan(np.asarray(og["g"])).all()
    assert np.isnan(np.asarray(oe["g"])).all()


def test_guard_dp_pair_expect_nonzero_off():
    """ZeRO per-device segments can be legitimately all-zero (padding
    rows): expect_nonzero=False must pass zeros through untouched."""
    z = {"g": jnp.zeros((3,))}
    og, oe = F.guard_dp_pair(z, z, expect_nonzero=False)
    assert not np.asarray(og["g"]).any()
    assert not np.isnan(np.asarray(oe["g"])).any()


# ---------------------------------------------------------------------------
# host-side attribution on synthetic states
# ---------------------------------------------------------------------------

COMM_FULL = CommConfig.from_dict({
    "mode": "aqsgd", "fw": {"bits": 4}, "bw": {"bits": 8},
    "dp": {"bits": 4, "wire": "ring"}})


def _clean_state():
    return {
        "params": {"w": jnp.ones((2, 2))},
        "opt": {"mu": {"w": jnp.zeros((2, 2))}},
        "dp_error": jnp.zeros((2, 8)),
        "buffers": {"seen": [jnp.asarray([True, False])],
                    "m": [jnp.ones((2, 4, 8), jnp.bfloat16)]},
    }


def _raises_plane(state, loss=None):
    with pytest.raises(F.WireFaultError) as e:
        F.check_train_state(state, comm=COMM_FULL, step=4, loss=loss)
    return e.value


def test_check_train_state_clean():
    assert F.check_train_state(_clean_state(), comm=COMM_FULL, step=1,
                               loss=2.5) is None


def test_attribution_dp_error():
    s = _clean_state()
    s["dp_error"] = s["dp_error"].at[0, 0].set(np.nan)
    err = _raises_plane(s)
    assert (err.plane, err.wire, err.step) == ("dp", "ring", 4)
    assert "dp_error" in err.detail
    assert "plane=dp wire='ring' step=4" in str(err)


def test_attribution_buffers_beat_dp_error():
    """Buffers are written from the forward pass — a later DP decode
    cannot contaminate them, so bad buffers attribute to fw even when
    the NaN also reached dp_error."""
    s = _clean_state()
    s["buffers"]["m"][0] = F.corrupt_array(s["buffers"]["m"][0],
                                           "nan-scale")
    s["dp_error"] = s["dp_error"].at[0, 0].set(np.nan)
    assert _raises_plane(s).plane == "fw"


def test_attribution_buffer_drop_hop_sentinel():
    s = _clean_state()
    s["buffers"]["m"][0] = jnp.zeros_like(s["buffers"]["m"][0])
    err = _raises_plane(s)
    assert err.plane == "fw"
    assert "all-zero stored message" in err.detail


def test_attribution_params_to_bw():
    s = _clean_state()
    s["params"]["w"] = F.corrupt_array(s["params"]["w"],
                                       "corrupt-codes")
    assert _raises_plane(s).plane == "bw"


def test_attribution_loss():
    err = _raises_plane(_clean_state(), loss=float("nan"))
    assert err.plane == "bw" and "loss" in err.detail


# ---------------------------------------------------------------------------
# serving batcher: slot-level isolation (kv plane)
# ---------------------------------------------------------------------------

def _serve_cfg():
    from repro.configs.base import get_config
    from repro.models import model as Mo
    cfg = get_config("gemma2-9b", smoke=True)
    return cfg, Mo.init_params(cfg, jax.random.PRNGKey(0))


def test_slot_flags():
    pool = {"pos": jnp.zeros((3,), jnp.int32),
            "k": jnp.zeros((2, 3, 4, 8), jnp.bfloat16),
            "codes": jnp.zeros((2, 3, 4), jnp.uint8)}
    assert not F.slot_flags(pool).any()
    pool["k"] = pool["k"].at[1, 2, 0, 0].set(np.nan)
    assert list(F.slot_flags(pool)) == [False, False, True]


def test_batcher_evicts_poisoned_slot_survivors_bit_identical():
    from repro.serving import ContinuousBatcher
    cfg, params = _serve_cfg()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in (3, 5, 4)]

    def serve(plan):
        bat = ContinuousBatcher(params, cfg, num_slots=2, cache_len=16,
                                fault_plan=plan)
        for p in prompts:
            bat.submit(p, max_new_tokens=6)
        return bat.run()

    base = serve(None)
    assert all(r.state == "DONE" and not r.error for r in base)
    hit = serve(F.FaultPlan.parse("2:kv:nan-scale"))
    victim, survivors = hit[0], hit[1:]
    assert victim.state == "DONE"
    assert "plane=kv" in victim.error and "tick=2" in victim.error
    assert len(victim.tokens) < 6         # cut short, not completed
    # vmapped row independence + full-row rewrite on re-admission:
    # every other request's stream is bit-identical to the clean run
    for b, h in zip(base[1:], survivors):
        assert not h.error
        assert h.tokens == b.tokens


def test_batcher_admission_guard_rejects_poisoned_prefill():
    from repro.serving import ContinuousBatcher
    cfg, params = _serve_cfg()
    params = jax.tree_util.tree_map(
        lambda l: F.corrupt_array(l, "nan-scale"), params)
    bat = ContinuousBatcher(params, cfg, num_slots=1, cache_len=16,
                            guard=True)
    req = bat.submit([1, 2, 3], max_new_tokens=4)
    bat.run(max_ticks=4)
    assert req.state == "DONE"
    assert "corrupt prefill payload" in req.error
    assert bat._slots == [None]           # never occupied a slot


# ---------------------------------------------------------------------------
# end-to-end through launch.runner (slow tier)
# ---------------------------------------------------------------------------

def _mk(comm_dict):
    from repro.configs.base import get_config
    from repro.optim.adamw import AdamWConfig
    from repro.training import simulated as sim
    cfg = get_config("gpt2-xl-paper", smoke=True)
    comm = CommConfig.from_dict(comm_dict)
    tcfg = sim.SimTrainConfig(num_stages=2, comm=comm,
                              optimizer=AdamWConfig(lr=1e-3,
                                                    warmup_steps=1,
                                                    total_steps=8),
                              dp_workers=2)
    return cfg, tcfg


def _run(cfg, tcfg, num_steps, *, ckpt_dir="", save_every=0,
         resume=False, fault=""):
    from repro.launch import runner
    ds = Dataset(DatasetConfig(num_samples=32, seq_len=32,
                               vocab_size=cfg.vocab_size))
    out = []
    state, losses = runner.run_sim_training(
        cfg, tcfg, ds, num_steps=num_steps, batch_size=4, log_every=1,
        ckpt_dir=ckpt_dir, save_every=save_every, resume=resume,
        fault_plan=F.FaultPlan.parse(fault),
        print_fn=lambda s: out.append(s))
    return losses, out


@pytest.mark.slow
def test_runner_matches_sim_train_bit_for_bit():
    """Checkpointing off + no faults: the runner IS `sim.train` — the
    same key discipline, the same jitted step, the same loss bits."""
    from repro.training import simulated as sim
    cfg, tcfg = _mk({"mode": "aqsgd", "fw": {"bits": 4},
                     "bw": {"bits": 8},
                     "dp": {"bits": 4, "wire": "ring"}})
    losses, _ = _run(cfg, tcfg, 6)
    ds = Dataset(DatasetConfig(num_samples=32, seq_len=32,
                               vocab_size=cfg.vocab_size))
    _, ref = sim.train(cfg, tcfg, ds, num_steps=6, batch_size=4)
    assert losses == ref


@pytest.mark.slow
@pytest.mark.parametrize("wire", ["psum", "ring", "ring-sharded"])
def test_kill_and_resume_bit_parity(wire, tmp_path):
    """The headline gate: train to step k with periodic checkpoints,
    'die', resume in a fresh call — the concatenated loss stream is
    bit-identical to the uninterrupted run.  EF + activation
    compression on, for every compressed DP wire."""
    cfg, tcfg = _mk({"mode": "aqsgd", "fw": {"bits": 4},
                     "bw": {"bits": 8},
                     "dp": {"bits": 4, "wire": wire}})
    base, _ = _run(cfg, tcfg, 8)
    d = str(tmp_path / wire)
    first, _ = _run(cfg, tcfg, 5, ckpt_dir=d, save_every=2)
    resumed, out = _run(cfg, tcfg, 8, ckpt_dir=d, resume=True)
    # the interrupted run commits a final step-5 checkpoint on exit;
    # mid-interval resume (replay overlap) is exercised by the fault
    # and CLI --kill-at gates below
    assert any(o.startswith("resumed from step 5") for o in out)
    assert first == base[:5]
    assert resumed == base[5:]


@pytest.mark.slow
@pytest.mark.parametrize("fault", [
    "4:dp:corrupt-codes", "4:dp:drop-hop", "4:fw:nan-scale",
    "4:bw:corrupt-codes", "4:zbuf:drop-hop"])
def test_fault_detect_attribute_recover_bit_parity(fault, tmp_path):
    """Inject on every plane: the guard names the injected plane/wire/
    step, recovery replays from the last good checkpoint, and the
    final loss stream is bit-identical to the clean run."""
    plane = fault.split(":")[1]
    comm_dict = {"mode": "aqsgd", "fw": {"bits": 4}, "bw": {"bits": 8},
                 "dp": {"bits": 4, "wire": "ring"}}
    if plane == "zbuf":
        comm_dict["zbuf"] = {"bits": 4}
    cfg, tcfg = _mk(comm_dict)
    base, _ = _run(cfg, tcfg, 8)
    d = str(tmp_path / "ck")
    losses, out = _run(cfg, tcfg, 8, ckpt_dir=d, save_every=2,
                       fault=fault)
    tripped = [o for o in out if o.startswith("guard tripped")]
    assert tripped, out
    assert f"plane={plane}" in tripped[0]
    assert "step=4" in tripped[0]
    assert any(o.startswith("recovered from checkpoint") for o in out)
    assert losses == base


@pytest.mark.slow
def test_fault_without_checkpoint_reraises():
    cfg, tcfg = _mk({"mode": "aqsgd", "fw": {"bits": 4},
                     "bw": {"bits": 8},
                     "dp": {"bits": 4, "wire": "ring"}})
    with pytest.raises(ValueError, match="--fault/--resume need"):
        _run(cfg, tcfg, 6, fault="3:dp:nan-scale")


# ---------------------------------------------------------------------------
# the real CLI: --kill-at (exit 17) then --resume (slow tier)
# ---------------------------------------------------------------------------

def _cli(extra, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--stages", "2", "--steps", "12", "--batch", "4",
         "--samples", "16", "--seq", "32", "--mode", "aqsgd",
         "--fw-bits", "4", "--bw-bits", "8", "--dp-grad-bits", "4",
         "--dp-wire", "ring"] + extra,
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.slow
def test_cli_kill_resume_bit_parity(tmp_path):
    base = _cli([])
    assert base.returncode == 0, base.stderr[-3000:]
    d = str(tmp_path / "ck")
    killed = _cli(["--ckpt-dir", d, "--save-every", "3",
                   "--kill-at", "7"])
    from repro.launch.runner import KILL_EXIT_CODE
    assert killed.returncode == KILL_EXIT_CODE, \
        (killed.returncode, killed.stdout, killed.stderr[-2000:])
    assert "killing at step 7" in killed.stdout
    resumed = _cli(["--ckpt-dir", d, "--save-every", "3", "--resume"])
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    assert "resumed from step 6" in resumed.stdout
    # step-10 line carries the loss bits (float.hex); final loss is
    # the mean of the last 5 — both must match the uninterrupted run
    base_lines = loss_lines(base.stdout)
    res_lines = loss_lines(resumed.stdout)
    assert res_lines == [ln for ln in base_lines
                         if not ln.startswith("step     0 ")]


@pytest.mark.slow
def test_cli_fault_recovers(tmp_path):
    d = str(tmp_path / "ck")
    base = _cli([])
    hit = _cli(["--ckpt-dir", d, "--save-every", "3",
                "--fault", "5:dp:nan-scale"])
    assert hit.returncode == 0, hit.stderr[-3000:]
    assert "guard tripped" in hit.stdout
    assert "plane=dp" in hit.stdout and "step=5" in hit.stdout
    assert "recovered from checkpoint step 3" in hit.stdout
    assert loss_lines(hit.stdout) == loss_lines(base.stdout)
