"""The one training loop (`launch.runner.run`) and what both trainers
hand it: the simulated trainer's state and step against the plain
reference loop `simulated.train`, the pipeline state builder against
the step's shardings, and kill-and-resume of the ``--distributed``
CLI through the loop."""
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.comm import CommConfig
from repro.configs.base import get_config
from repro.data.pipeline import Dataset, DatasetConfig
from workerproc import ROOT, loss_lines


@pytest.mark.slow
def test_loop_handed_given_weights_matches_sim_train():
    """A state built by the caller, its params replaced by a copy of
    given weights, run through `runner.run` with the simulated step:
    the loss stream is `sim.train(..., initial_params=...)`'s, bit for
    bit."""
    from repro.launch import runner
    from repro.models import model as Mo
    from repro.optim.adamw import AdamWConfig
    from repro.training import simulated as sim
    cfg = get_config("gpt2-xl-paper", smoke=True)
    tcfg = sim.SimTrainConfig(
        num_stages=2, comm=CommConfig.from_dict(
            {"mode": "aqsgd", "fw": {"bits": 4}, "bw": {"bits": 8}}),
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=6))
    dc = DatasetConfig(num_samples=16, seq_len=32,
                       vocab_size=cfg.vocab_size)
    ds = Dataset(dc)
    weights = Mo.init_params(cfg, jax.random.PRNGKey(7))

    k_init, k_run = jax.random.split(jax.random.PRNGKey(0))
    state = sim.init_train_state(cfg, tcfg, ds.num_samples,
                                 ds.dc.seq_len, k_init)
    state["params"] = jax.tree.map(jnp.copy, weights)
    lines = []
    _, losses = runner.run(
        lambda st, batch, step: sim.train_step(
            st, batch, jax.random.fold_in(k_run, step), mcfg=cfg,
            tcfg=tcfg),
        state, ds, comm=tcfg.comm, key=k_run,
        num_steps=6, batch_size=4, log_every=1, print_fn=lines.append)

    # a fresh dataset: `Dataset.batches` is a stateful stream
    _, ref = sim.train(cfg, tcfg, Dataset(dc), num_steps=6, batch_size=4,
                       initial_params=weights)
    assert losses == ref
    assert len(lines) == 6
    # the given weights moved the start: not the seed's own init
    _, seeded = sim.train(cfg, tcfg, Dataset(dc), num_steps=1,
                          batch_size=4)
    assert seeded[0] != ref[0]


@pytest.mark.parametrize("wire,zbits,opt_bits,dtype", [
    ("ring", 0, 0, "bfloat16"),          # dense moments, raw buffers
    ("ring-sharded", 4, 0, "float32"),   # ZeRO moments, z-bit buffers
    ("psum", 0, 8, "float32"),           # quantized moments
])
def test_pipeline_state_builder_is_the_structs(wire, zbits, opt_bits,
                                               dtype):
    """`make_state_structs`'s state is `pipeline.init_train_state`'s
    eval_shape; the built state lands on the step's shardings leaf for
    leaf, with the layout the step traces against."""
    from repro.launch.mesh import make_debug_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.training import pipeline as PL
    cfg = get_config("gpt2-xl-paper", smoke=True).with_(dtype=dtype)
    comm = CommConfig.from_dict({
        "mode": "aqsgd", "fw": {"bits": 4}, "bw": {"bits": 8},
        "zbuf": {"bits": zbits}, "dp": {"bits": 4, "wire": wire}})
    pcfg = PL.PipelineConfig(microbatches=2, comm=comm)
    mesh = make_debug_mesh(1, 1)
    gb, seq, n = 4, 32, 8
    _, meta = PL.make_train_step(cfg, pcfg, mesh,
                                 AdamWConfig(state_bits=opt_bits),
                                 global_batch=gb, seq_len=seq,
                                 buffer_samples=n)
    structs, _, _ = PL.make_state_structs(
        cfg, pcfg, meta, mesh, global_batch=gb, seq_len=seq,
        opt_state_bits=opt_bits)
    key = jax.random.PRNGKey(0)
    build = lambda: PL.init_train_state(cfg, pcfg, meta, mesh, key,
                                        opt_state_bits=opt_bits)
    assert jax.eval_shape(build) == structs

    specs = meta["state_specs"]
    assert (jax.tree.structure(structs)
            == jax.tree.structure(specs, is_leaf=lambda x: hasattr(
                x, "spec")))
    state = jax.jit(build, out_shardings=specs)()
    for got, want, spec in zip(jax.tree.leaves(state),
                               jax.tree.leaves(structs),
                               jax.tree.leaves(specs)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
        assert got.sharding == spec

    # the layout itself: params in cfg.dtype on the step's param shapes;
    # f32 moments; one EF row stack per DP rank; buffers per stage
    shapes = jax.tree.map(lambda s: s.shape, meta["params_shape"])
    assert jax.tree.map(lambda s: s.shape, structs["params"]) == shapes
    assert {s.dtype for s in jax.tree.leaves(structs["params"])} \
        == {jnp.dtype(dtype)}
    mu = structs["opt"]["mu"]
    if wire == "ring-sharded":
        assert mu.ndim == 3 and mu.shape[0] == 1
        assert mu.dtype == jnp.float32
    elif opt_bits:
        assert {s.dtype for s in jax.tree.leaves(mu)} \
            == {jnp.dtype(jnp.uint8), jnp.dtype(jnp.float32)}
    else:
        assert jax.tree.map(lambda s: s.shape, mu) == shapes
        assert {s.dtype for s in jax.tree.leaves(mu)} \
            == {jnp.dtype(jnp.float32)}
    assert structs["dp_error"].shape[0] == 1
    for name in ("m_out", "m_in"):
        for s in jax.tree.leaves(structs[name]):
            assert s.shape[:3] == (1, n, seq)


def _cli(extra, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--distributed", "--stages", "2", "--data-par", "1",
         "--steps", "12", "--batch", "4", "--samples", "8",
         "--seq", "32", "--microbatches", "2"] + extra,
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.slow
def test_distributed_cli_kill_resume_bit_parity(tmp_path):
    """`--distributed` through the loop: killed mid checkpoint interval
    (`--kill-at`, exit 17) and resumed, the loss lines are the
    uninterrupted run's.  The uninterrupted run also takes a profile
    (`--profile-dir`), which no longer refuses `--distributed`."""
    from repro.launch.runner import KILL_EXIT_CODE
    prof = str(tmp_path / "prof")
    base = _cli(["--profile-dir", prof, "--profile-steps", "4:6"])
    assert base.returncode == 0, base.stderr[-3000:]
    assert len(glob.glob(os.path.join(prof, "**", "*.xplane.pb"),
                         recursive=True)) == 1
    d = str(tmp_path / "ck")
    killed = _cli(["--ckpt-dir", d, "--save-every", "2",
                   "--kill-at", "3"])
    assert killed.returncode == KILL_EXIT_CODE, \
        (killed.returncode, killed.stdout, killed.stderr[-2000:])
    assert "killing at step 3" in killed.stdout
    resumed = _cli(["--ckpt-dir", d, "--save-every", "2", "--resume"])
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    assert "resumed from step 2" in resumed.stdout
    base_lines = loss_lines(base.stdout)
    res_lines = loss_lines(resumed.stdout)
    assert any("[" in ln for ln in res_lines), res_lines
    assert res_lines == [ln for ln in base_lines
                         if not ln.startswith("step     0 ")]


def test_distributed_cli_refuses_fault(monkeypatch, capsys):
    """Fault injection stays the simulated trainer's."""
    from repro.launch import train
    monkeypatch.setattr(sys, "argv", [
        "train", "--smoke", "--distributed", "--ckpt-dir", "unused",
        "--fault", "3:dp:nan-scale"])
    with pytest.raises(SystemExit) as e:
        train.main()
    assert e.value.code == 2
    assert "--fault targets the single-host" in capsys.readouterr().err
