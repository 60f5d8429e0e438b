"""Subprocess worker: distributed pipeline correctness on 4 host devices.

Run as: python tests/workers/pipeline_worker.py <check>
Checks:
  fp32_equivalence — pipeline fp32 loss == monolithic loss_fn loss
  aqsgd_buffers    — warmup step fills buffers with boundary activations;
                     compressed steps then train with finite losses and a
                     shrinking delta magnitude
  modes_all_archs  — one pipeline step for dense/moe/ssm/hybrid/audio/vlm
  kernels_under_rows_over — the flash kernels (interpret mode) in the
                     GSPMD section, per data shard: losses == the scan's
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.config import CommConfig
from repro.configs.base import get_config
from repro.core.aqsgd import CompressionConfig
from repro.launch.mesh import make_debug_mesh
from repro.models import model as Mo
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.training import pipeline as PL


def build(arch, mode, *, num_layers=None, warmup=False, M=2, Bg=4, S=32,
          lr=0.0, buffer_bits=0, dp_grad_bits=0, dp_wire="ring",
          dp_chunks=1, **overrides):
    cfg = get_config(arch, smoke=True).with_(**overrides)
    if num_layers:
        cfg = cfg.with_(num_layers=num_layers)
    mesh = make_debug_mesh(2, 2)
    comm = CommConfig.from_legacy(
        CompressionConfig(mode=mode, fw_bits=4, bw_bits=8),
        buffer_bits=buffer_bits, dp_grad_bits=dp_grad_bits,
        dp_wire=dp_wire)
    if dp_chunks != 1:
        comm = comm.with_(dp=comm.dp.with_(chunks=dp_chunks))
    pcfg = PL.PipelineConfig(
        microbatches=M, warmup=warmup, remat=True, comm=comm)
    step, meta = PL.make_train_step(
        cfg, pcfg, mesh, AdamWConfig(lr=lr, warmup_steps=1,
                                     schedule="constant"),
        global_batch=Bg, seq_len=S, buffer_samples=Bg // 2)
    params = PL.to_pipeline_params(
        cfg, Mo.init_params(cfg, jax.random.PRNGKey(0)), 2)
    if dp_grad_bits and dp_wire == "ring-sharded":
        opt_state = PL.init_sharded_opt(pcfg, params, 2)
    else:
        opt_state = adamw.init_opt_state(params)
    state = {"params": params, "opt": opt_state}
    if dp_grad_bits:
        state["dp_error"] = PL.init_dp_error(pcfg, params, 2)
    if mode == "aqsgd":
        trunk_seq = meta["trunk_seq"]
        if buffer_bits:
            structs = PL.buffer_structs(pcfg, 2, Bg, trunk_seq,
                                        cfg.d_model)
            state["m_out"] = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), structs)
            state["m_in"] = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), structs)
        else:
            state["m_out"] = jnp.zeros((2, Bg, trunk_seq, cfg.d_model),
                                       jnp.bfloat16)
            state["m_in"] = jnp.zeros_like(state["m_out"])
    n_text = S - (cfg.num_patches or 0)
    bmb = Bg // M
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1),
                                     (M, bmb, n_text), 0, cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2),
                                      (M, bmb, n_text), 0, cfg.vocab_size),
        "mask": jnp.ones((M, bmb, n_text), jnp.float32),
        "sample_ids": (jnp.arange(Bg, dtype=jnp.int32)
                       % (Bg // 2)).reshape(M, bmb),
    }
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(4), (M, bmb, cfg.num_patches, cfg.d_model),
            jnp.float32) * 0.02
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(5), (M, bmb, cfg.encoder_seq, cfg.d_model),
            jnp.float32) * 0.02
    return cfg, step, state, batch


def check_fp32_equivalence():
    arch = "gpt2-xl-paper"
    cfg, step, state, batch = build(arch, "fp32", num_layers=4)
    _, metrics = step(state, batch, jax.random.PRNGKey(3))
    pipe_loss = float(metrics["loss"])
    params = Mo.init_params(cfg.with_(num_layers=4), jax.random.PRNGKey(0))
    flat = {k: v.reshape(-1, *v.shape[2:]) for k, v in batch.items()}
    ref_loss, _ = Mo.loss_fn(params, cfg.with_(num_layers=4), flat)
    print("pipe", pipe_loss, "ref", float(ref_loss))
    np.testing.assert_allclose(pipe_loss, float(ref_loss), rtol=2e-4)
    print("OK fp32_equivalence")


def check_aqsgd_buffers():
    cfg, step, state, batch = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                                    warmup=True, lr=1e-3)
    key = jax.random.PRNGKey(3)
    state1, m1 = step(state, batch, key)
    assert float(jnp.sum(jnp.abs(state1["m_out"].astype(jnp.float32)))) > 0
    # m_in of stage k must equal m_out of stage k-1 (bit-identical copies)
    mo = np.asarray(state1["m_out"].astype(jnp.float32))
    mi = np.asarray(state1["m_in"].astype(jnp.float32))
    np.testing.assert_allclose(mi[1], mo[0], atol=0)
    # compressed steps after warmup
    cfg2, step2, _, _ = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                              warmup=False, lr=1e-3)
    losses = []
    st = state1
    for i in range(4):
        st, met = step2(st, batch, jax.random.fold_in(key, i))
        losses.append(float(met["loss"]))
        np.testing.assert_allclose(
            np.asarray(st["m_in"].astype(jnp.float32))[1],
            np.asarray(st["m_out"].astype(jnp.float32))[0], atol=0)
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    print("OK aqsgd_buffers", losses)


def check_zbit_buffers():
    """§H.5 z-bit stored messages through the real pipeline: the fused
    buffer codec keeps both replicas' codes bit-identical and training
    stays finite."""
    cfg, step, state, batch = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                                    warmup=True, lr=1e-3, buffer_bits=4)
    key = jax.random.PRNGKey(3)
    st, _ = step(state, batch, key)
    assert int(jnp.sum(st["m_out"]["codes"])) > 0
    np.testing.assert_array_equal(np.asarray(st["m_in"]["codes"])[1],
                                  np.asarray(st["m_out"]["codes"])[0])
    _, step2, _, _ = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                           warmup=False, lr=1e-3, buffer_bits=4)
    losses = []
    for i in range(3):
        st, met = step2(st, batch, jax.random.fold_in(key, i))
        losses.append(float(met["loss"]))
        np.testing.assert_array_equal(
            np.asarray(st["m_in"]["codes"])[1],
            np.asarray(st["m_out"]["codes"])[0])
        np.testing.assert_array_equal(
            np.asarray(st["m_in"]["scale"])[1],
            np.asarray(st["m_out"]["scale"])[0])
    assert np.all(np.isfinite(losses)), losses
    print("OK zbit_buffers", losses)


def check_modes_all_archs():
    for arch in ["gemma2-9b", "deepseek-moe-16b", "mamba2-1.3b",
                 "zamba2-2.7b", "whisper-small", "pixtral-12b"]:
        cfg, step, state, batch = build(arch, "aqsgd", lr=1e-3)
        _, metrics = step(state, batch, jax.random.PRNGKey(3))
        l = float(metrics["loss"])
        assert np.isfinite(l), (arch, l)
        print("OK", arch, l)
    print("OK modes_all_archs")





def check_dp_grad_pipeline():
    """Fig. 5 end-to-end mode through the real shard_map pipeline: the
    compressed DP gradient wire (bucketed codec + int32 code psum +
    per-rank error feedback) trains with finite decreasing losses, and
    the carried error state becomes active after the first step."""
    cfg, step, state, batch = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                                    warmup=True, lr=1e-3, dp_grad_bits=4)
    key = jax.random.PRNGKey(3)
    st, _ = step(state, batch, key)
    assert float(jnp.sum(jnp.abs(st["dp_error"]))) > 0
    _, step2, _, _ = build("gpt2-xl-paper", "aqsgd", num_layers=4,
                           warmup=False, lr=1e-3, dp_grad_bits=4)
    losses = []
    for i in range(4):
        st, met = step2(st, batch, jax.random.fold_in(key, i))
        losses.append(float(met["loss"]))
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    print("OK dp_grad_pipeline", losses)


def check_dp_wire_parity():
    """All three DP gradient wires through the REAL pipeline train
    step, from the same initial state and batch stream:

    * ``psum`` vs ``ring`` — bit-identical losses at every step (the
      programs differ only inside the collective; int32 code sums are
      exact in any order);
    * chunked ``ring`` / ``ring-sharded`` (``dp.chunks=2``, the
      double-buffered schedule) — bit-identical losses to their
      monolithic forms at every step (chunking is scheduling only);
    * ``ring`` vs ``ring-sharded`` — bit-identical losses while the
      trajectories coincide (first steps), then tracking at ulp level:
      the sharded program replaces the pjit-level per-leaf AdamW with
      the fused in-shard_map segment update, and XLA fuses the
      surrounding model backward differently — the same documented
      drift class as swapping codec backends (see core/boundary.py),
      NOT codec divergence.  The collective itself is pinned bit-exact
      against ring/psum/sim in dp_grad_worker.py.

    This check also regresses the GSPMD flatten-bucket doubling bug
    (`pipeline.replicate_leaves`): without the replication pin, every
    wire ships a 2x gradient bucket on meshes with model > 1 and the
    sharded trajectory separates immediately and grossly."""
    runs = {}
    for wire, chunks in (("psum", 1), ("ring", 1), ("ring-sharded", 1),
                         ("ring", 2), ("ring-sharded", 2)):
        cfg, step, state, batch = build(
            "gpt2-xl-paper", "aqsgd", num_layers=4, warmup=False,
            lr=1e-3, dp_grad_bits=4, dp_wire=wire, dp_chunks=chunks)
        key = jax.random.PRNGKey(3)
        losses = []
        for i in range(4):
            state, met = step(state, batch, jax.random.fold_in(key, i))
            losses.append(float(met["loss"]))
        runs[wire if chunks == 1 else f"{wire}/K{chunks}"] = losses
    assert runs["psum"] == runs["ring"], (runs["psum"], runs["ring"])
    # the chunked double-buffered schedule is scheduling only: losses
    # bit-identical to the monolithic wires at every step
    assert runs["ring/K2"] == runs["ring"], \
        (runs["ring/K2"], runs["ring"])
    assert runs["ring-sharded/K2"] == runs["ring-sharded"], \
        (runs["ring-sharded/K2"], runs["ring-sharded"])
    # sharded: exact while trajectories coincide, tight thereafter
    assert runs["ring-sharded"][:2] == runs["ring"][:2], \
        (runs["ring-sharded"], runs["ring"])
    np.testing.assert_allclose(runs["ring-sharded"], runs["ring"],
                               rtol=2e-3)
    assert all(np.isfinite(v) for v in runs["ring-sharded"])
    print("OK dp_wire_parity", runs["ring"], runs["ring-sharded"])


def check_dp_wire_fp16():
    """The registry-only fp16 passthrough wire through the REAL
    pipeline train step: `make_dp_grad_wire` resolves it from the wire
    registry with zero trainer special-casing (nothing in
    core/collectives.py knows it exists), and it trains with finite
    decreasing losses that track the codec wires loosely (same
    gradients up to f16 rounding vs 4-bit EF quantization)."""
    cfg, step, state, batch = build(
        "gpt2-xl-paper", "aqsgd", num_layers=4, warmup=False, lr=1e-3,
        dp_grad_bits=4, dp_wire="fp16")
    key = jax.random.PRNGKey(3)
    losses = []
    for i in range(4):
        state, met = step(state, batch, jax.random.fold_in(key, i))
        losses.append(float(met["loss"]))
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # the cast-error feedback state becomes active after one step
    assert float(jnp.sum(jnp.abs(state["dp_error"]))) > 0
    print("OK dp_wire_fp16", losses)


def check_expert_parallel():
    """EP MoE == ZeRO-3 MoE numerically (no-drop capacity), and the
    pipeline still trains."""
    import repro.training.pipeline as PLmod

    def build_ep(moe_mode):
        cfg = get_config("deepseek-moe-16b", smoke=True)
        mesh = make_debug_mesh(2, 2)
        pcfg = PL.PipelineConfig(
            microbatches=2, moe_mode=moe_mode,
            comm=CommConfig.from_legacy(CompressionConfig(mode="fp32")))
        step, meta = PL.make_train_step(
            cfg, pcfg, mesh, AdamWConfig(lr=0.0, warmup_steps=1,
                                         schedule="constant"),
            global_batch=4, seq_len=32, buffer_samples=2)
        params = PL.to_pipeline_params(
            cfg, Mo.init_params(cfg, jax.random.PRNGKey(0)), 2)
        state = {"params": params, "opt": adamw.init_opt_state(params)}
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1),
                                         (2, 2, 32), 0, cfg.vocab_size),
            "targets": jax.random.randint(jax.random.PRNGKey(2),
                                          (2, 2, 32), 0, cfg.vocab_size),
            "mask": jnp.ones((2, 2, 32), jnp.float32),
            "sample_ids": jnp.arange(4, dtype=jnp.int32).reshape(2, 2),
        }
        _, metrics = step(state, batch, jax.random.PRNGKey(3))
        return float(metrics["loss"])

    l_z3 = build_ep("zero3")
    l_ep = build_ep("expert_parallel")
    print("zero3", l_z3, "ep", l_ep)
    np.testing.assert_allclose(l_ep, l_z3, rtol=1e-4)
    print("OK expert_parallel")


def check_kernels_under_rows_over():
    """deepseek-moe's dense prefix layer and whisper's encoder run in the
    train step's GSPMD section, where the flash kernels run per data
    shard under `layers.rows_over` (on a TPU: GSPMD cannot partition a
    Mosaic call).  Routed to the kernels here, in interpret mode, three
    training steps on the (2, 2) mesh give the scan's losses: the
    shard_map's gradient over the replicated model axis is summed once,
    not once per model rank."""
    import types
    from repro.models import layers as L
    real_env, real_attention = L.env, L._kernel_attention
    for arch, kw in [("deepseek-moe-16b", {}),
                     ("whisper-small", {"encoder_seq": 128})]:
        losses = {}
        for path in ("kernels", "scan"):
            sharded = []

            def spy(*a, **k):
                sharded.append(bool(L._ROWS) and not
                               jax.sharding.get_abstract_mesh().manual_axes)
                return real_attention(*a, **k)

            L.env = types.SimpleNamespace(
                pallas_interpret=lambda: path == "scan")
            L._kernel_attention = spy
            try:
                cfg, step, state, batch = build(arch, "aqsgd", warmup=True,
                                                lr=1e-3, S=128, **kw)
                losses[path] = []
                for i in range(3):
                    state, m = step(state, batch, jax.random.PRNGKey(3 + i))
                    losses[path].append(float(m["loss"]))
            finally:
                L.env, L._kernel_attention = real_env, real_attention
            assert any(sharded) == (path == "kernels"), (arch, path, sharded)
        print(arch, losses)
        np.testing.assert_allclose(losses["kernels"], losses["scan"],
                                   rtol=1e-5)
    print("OK kernels_under_rows_over")


if __name__ == "__main__":
    globals()["check_" + sys.argv[1]]()
