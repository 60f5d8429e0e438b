"""Training launcher.

Single-host execution drives the bit-faithful simulated pipeline (the
science path); passing --distributed uses the shard_map GPipe pipeline on
whatever devices exist (set XLA_FLAGS=--xla_force_host_platform_device_count=N
for CPU experiments; on TPU pods it runs as-is).

All communication knobs are one `repro.comm.CommConfig`: the flat flags
below (--mode/--fw-bits/--bw-bits/--buffer-bits/--dp-grad-bits/
--dp-wire/...) build it, or pass the whole thing as JSON with
--comm-config (a literal string or a path).  --dp-wire choices and
their help one-liners come from the wire registry; --list-wires prints
the full registry table (every plane, every wire, its byte model).

Examples:
  python -m repro.launch.train --arch gpt2-xl-paper --smoke \\
      --mode aqsgd --fw-bits 4 --bw-bits 8 --steps 100
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  python -m repro.launch.train --arch gemma2-9b --smoke --distributed \\
      --data-par 4 --stages 2 --steps 10
  python -m repro.launch.train --smoke --steps 10 \\
      --comm-config '{"mode": "aqsgd", "dp": {"bits": 4, "wire": "fp16"}}'
  python -m repro.launch.train --smoke --steps 30 \\
      --profile-dir /tmp/prof --profile-steps 10:20
"""
from __future__ import annotations

import argparse

import numpy as np


def print_wires() -> None:
    """The --list-wires table: every registered wire, from the
    registry metadata (the same source the --dp-wire help uses)."""
    from repro.comm import list_wires
    rows = [(s.plane, s.name,
             ("sharded" if s.sharded else "") +
             ("" if s.network else "local"),
             s.summary) for s in list_wires()]
    wp = max(len(r[0]) for r in rows)
    wn = max(len(r[1]) for r in rows)
    wf = max(len(r[2]) for r in rows)
    print(f"{'plane':{wp}}  {'wire':{wn}}  {'':{wf}}  summary")
    for p, n, f, s in rows:
        print(f"{p:{wp}}  {n:{wn}}  {f:{wf}}  {s}")


def _step_range(text: str) -> tuple:
    """``A:B`` -> (A, B), the steps A to B-1."""
    try:
        a, b = (int(x) for x in text.split(":"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected A:B, got {text!r}")
    if not 0 <= a < b:
        raise argparse.ArgumentTypeError(f"need 0 <= A < B, got {text!r}")
    return a, b


def main():
    from repro.comm import config as comm_cli

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-xl-paper")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    comm_cli.add_cli_args(ap)
    ap.add_argument("--list-wires", action="store_true",
                    help="print the wire registry table and exit")
    ap.add_argument("--dp-workers", type=int, default=2,
                    help="simulated DP degree for --dp-grad-bits in the "
                         "single-host trainer")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup-epochs", type=int, default=1)
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--data-par", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--checkpoint", default="",
                    help="legacy params-only .npz export at exit "
                         "(full-state checkpointing is --ckpt-dir)")
    ap.add_argument("--corpus", default="",
                    help="optional text file to train on (byte-level)")
    ap.add_argument("--ckpt-dir", default="",
                    help="versioned full-state checkpoint directory "
                         "(repro.checkpoint manifest subsystem)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the FULL train state every N "
                         "steps (0 = off; needs --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest committed checkpoint "
                         "in --ckpt-dir (checksums, structure and "
                         "comm config are verified; the replayed loss "
                         "stream is bit-identical)")
    ap.add_argument("--keep", type=int, default=3,
                    help="keep-last-k checkpoint rotation (0 = keep "
                         "all)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="bounded fault recovery: reload the last "
                         "good checkpoint and replay at most this "
                         "many times")
    ap.add_argument("--fault", default="",
                    help="deterministic fault injection plan, "
                         "step:plane:kind[,...] — e.g. "
                         "'3:dp:nan-scale,5:fw:drop-hop' (kinds: "
                         "corrupt-codes, nan-scale, drop-hop; "
                         "single-host trainer only)")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler trace of --profile-steps "
                         "under this directory: the device ops beside "
                         "the host spans repro.step > feed, dispatch, "
                         "sync, guard (single-host trainer only)")
    ap.add_argument("--profile-steps", type=_step_range, default=(1, 11),
                    metavar="A:B",
                    help="steps A to B-1 to profile (default 1:11, "
                         "which leaves out step 0's compile)")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="hard-exit (os._exit 17) right after "
                         "printing step N's loss, before any save — "
                         "the kill half of the kill-and-resume parity "
                         "gate (single-host trainer only)")
    args = ap.parse_args()

    if args.list_wires:
        print_wires()
        return

    from repro import env
    from repro.configs.base import get_config
    from repro.data.pipeline import Dataset, DatasetConfig
    from repro.optim.adamw import AdamWConfig
    from repro.checkpoint import checkpoint as ckpt

    env.use_compile_cache()
    comm = comm_cli.from_args(args)
    cfg = get_config(args.arch, smoke=args.smoke)
    dc = DatasetConfig(num_samples=args.samples, seq_len=args.seq,
                       vocab_size=cfg.vocab_size,
                       kind="textfile" if args.corpus else "synthetic-lm",
                       path=args.corpus or None)
    ds = Dataset(dc)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                      total_steps=args.steps)

    if args.fault and args.distributed:
        ap.error("--fault targets the single-host simulated trainer")
    if args.kill_at is not None and args.distributed:
        ap.error("--kill-at targets the single-host simulated trainer")
    if args.profile_dir and args.distributed:
        ap.error("--profile-dir targets the single-host simulated trainer")
    if (args.resume or args.save_every or args.fault) \
            and not args.ckpt_dir:
        ap.error("--resume/--save-every/--fault need --ckpt-dir")

    if not args.distributed:
        from repro import tracing
        from repro.comm.faults import FaultPlan
        from repro.launch import runner
        from repro.training import simulated as sim
        tcfg = sim.SimTrainConfig(num_stages=args.stages, comm=comm,
                                  optimizer=opt,
                                  dp_workers=args.dp_workers
                                  if comm.dp.bits else 1)
        state, losses = runner.run_sim_training(
            cfg, tcfg, ds, num_steps=args.steps,
            batch_size=args.batch, log_every=10,
            ckpt_dir=args.ckpt_dir, save_every=args.save_every,
            keep=args.keep, resume=args.resume,
            max_retries=args.max_retries,
            fault_plan=FaultPlan.parse(args.fault),
            kill_at=args.kill_at,
            profile=tracing.StepProfile(args.profile_dir,
                                        *args.profile_steps)
            if args.profile_dir else None)
        print(f"final loss {np.mean(losses[-5:]):.4f}")
        if args.checkpoint:
            ckpt.save(args.checkpoint, state["params"])
            print("saved", args.checkpoint)
        return

    run_distributed(cfg, comm, ds, opt, stages=args.stages,
                    data_par=args.data_par,
                    microbatches=args.microbatches, batch=args.batch,
                    seq=args.seq, samples=args.samples,
                    steps=args.steps, warmup_epochs=args.warmup_epochs,
                    ckpt_dir=args.ckpt_dir, save_every=args.save_every,
                    keep=args.keep, resume=args.resume)


def run_distributed(cfg, comm, ds, opt, *, stages: int, data_par: int,
                    microbatches: int, batch: int, seq: int, samples: int,
                    steps: int, warmup_epochs: int = 1, ckpt_dir: str = "",
                    save_every: int = 0, keep: int = 3,
                    resume: bool = False, log_every: int = 10,
                    key=None, print_fn=print):
    """The shard_map GPipe pipeline on a (data_par, stages) mesh of the
    visible devices — the ``--distributed`` path.  Returns ``(state,
    losses)``, one loss per step this call ran."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import checkpoint as ckpt
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as Mo
    from repro.optim import adamw
    from repro.training import pipeline as PL

    mesh = make_debug_mesh(data_par, stages)
    steps_w = {}
    for warm in (True, False):
        pcfg = PL.PipelineConfig(microbatches=microbatches, comm=comm,
                                 warmup=warm)
        steps_w[warm], meta = PL.make_train_step(
            cfg, pcfg, mesh, opt, global_batch=batch, seq_len=seq,
            buffer_samples=samples // data_par)

    def init_state():
        params = PL.to_pipeline_params(
            cfg, Mo.init_params(cfg, key if key is not None
                                else jax.random.PRNGKey(0)), stages)
        if comm.dp.bits and comm.dp_wire_spec.sharded:
            opt_state = PL.init_sharded_opt(pcfg, params, data_par)
        else:
            opt_state = adamw.init_opt_state(params)
        state = {"params": params, "opt": opt_state}
        if comm.dp.bits:
            state["dp_error"] = PL.init_dp_error(pcfg, params, data_par)
        if comm.mode == "aqsgd":
            n_loc = samples // data_par
            structs = PL.buffer_structs(pcfg, stages, data_par * n_loc,
                                        seq, cfg.d_model)
            zeros = lambda s: jnp.zeros(s.shape, s.dtype)
            state["m_out"] = jax.tree.map(zeros, structs)
            state["m_in"] = jax.tree.map(zeros, structs)
        return state

    # built in place on the step's shardings: each device holds only its
    # own stage's slice, never the whole model
    state = jax.jit(init_state, out_shardings=meta["state_specs"])()

    start = 0
    if ckpt_dir:
        removed = ckpt.clean_orphans(ckpt_dir)
        if removed:
            print_fn(f"checkpoint: removed {len(removed)} orphaned tmp "
                     f"entries")
    if resume:
        state, body = ckpt.restore_state(ckpt_dir,
                                         jax.eval_shape(lambda: state),
                                         comm=comm)
        start = int(body["step"])
        print_fn(f"resumed from step {start}")

    m = microbatches
    steps_per_epoch = max(samples // batch, 1)
    step_key = jax.random.PRNGKey(1)
    ds.reset()          # the batch stream is a function of the config
    batches = ds.batches(batch, steps)
    for _ in range(start):
        next(batches)   # the data stream is deterministic: replay by
                        # skipping to the checkpointed position
    losses = []
    for step_i, b in enumerate(batches, start=start):
        b = {k: jnp.asarray(v).reshape(m, batch // m, *v.shape[1:])
             for k, v in b.items()}
        warm = comm.mode == "aqsgd" \
            and step_i < steps_per_epoch * warmup_epochs
        state, metrics = steps_w[warm](state, b,
                                       jax.random.fold_in(step_key, step_i))
        loss = float(metrics["loss"])
        losses.append(loss)
        if log_every and step_i % log_every == 0:
            print_fn(f"step {step_i:5d} loss {loss:.4f} [{loss.hex()}]")
        done = step_i + 1
        if ckpt_dir and save_every and done % save_every == 0:
            ckpt.save_state(ckpt_dir, state, step=done, comm=comm,
                            extra={"data_position": done}, keep=keep)
    if losses:
        print_fn(f"final loss {losses[-1]:.4f}")
    return state, losses

if __name__ == "__main__":
    main()
