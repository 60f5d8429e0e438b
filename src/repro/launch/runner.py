"""The one training loop of the CLI, and the simulated trainer's half.

`run` drives a step function over a built state.  The trainer hands it
three things: the state, a step ``(state, batch, step) -> (state,
metrics)`` that closes over its own key and batch shape, and the key
its checkpoints must match.  `run_sim_training` builds the
simulated trainer's state and step; `launch.train.run_distributed`
builds the shard_map pipeline's.  Around every step the loop owns:

* **checkpoint** — every ``save_every`` steps (plus step 0 at init and
  the final step) the FULL state — params, opt (incl. segment-sharded
  moments), the AQ-SGD message buffers, the ``dp_error`` EF carry —
  is committed via `repro.checkpoint.save_state` together with the
  key data, the data-pipeline position, and the recent loss tail;
  ``keep`` rotates old checkpoints out;
* **resume** — `restore_state` verifies checksums + structure + comm
  config, the stored key data is CHECKED against the live one (a
  resume under a different seed fails loudly instead of silently
  forking the trajectory), and the deterministic `data.pipeline`
  stream is replayed by skipping the first ``step`` batches;
* **inject** — a `repro.comm.faults.FaultPlan` fires at its (step,
  plane) coordinates through two hooks the trainer passes:
  ``faulted_step(spec)`` is the step to run instead for a dp fault
  (the simulated trainer swaps the internal fault-wrapper wire into a
  replaced static config for exactly that step, so clean steps keep
  the original compiled executable), and ``inject(state, spec)``
  corrupts the carried state for fw/bw/zbuf faults.  Each fault fires
  ONCE — the post-recovery replay of the same step runs clean;
* **recover** — after every step the loss (always) and the state
  (when a fault plan or checkpointing is active) pass through
  `check_train_state`; a `WireFaultError` reloads the last good
  checkpoint and replays, at most ``max_retries`` times, then
  re-raises.

``kill_at=k`` hard-exits the process (``os._exit(17)``) right after
printing step k's loss and BEFORE any save — the crash lands mid
checkpoint interval, which is exactly what the kill-and-resume
bit-parity gate needs to prove replay determinism.

Loss lines carry both the rounded value and ``float.hex()`` so the
CLI parity gates compare exact bits, not printed digits.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import tracing
from repro.comm import faults as F

KILL_EXIT_CODE = 17   # --kill-at's os._exit status: distinguishable
                      # from both success and a python traceback


def _key_data(key) -> np.ndarray:
    """Raw uint32 words of a PRNG key (typed or old-style)."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(key))
    except (AttributeError, TypeError):
        pass
    return np.asarray(key)


def _skip_batches(dataset, batch_size: int, num_steps: int,
                  start: int):
    """The deterministic batch stream starting at step ``start`` —
    `Dataset.reset` rewinds the epoch-shuffle rng to its seed, so the
    stream is a pure function of the config and resume/replay is
    reset-and-skip, no cursor state to persist."""
    dataset.reset()
    it = dataset.batches(batch_size, num_steps)
    for _ in range(start):
        next(it)
    return it


def _loss_line(step: int, loss: float) -> str:
    return (f"step {step:5d} loss {loss:.4f} "
            f"[{float(loss).hex()}]")


def run(step_fn: Callable, state, dataset, *, comm, key,
        num_steps: int, batch_size: int, log_every: int = 10,
        ckpt_dir: str = "", save_every: int = 0, keep: int = 3,
        resume: bool = False, max_retries: int = 2,
        fault_plan: Optional[F.FaultPlan] = None,
        faulted_step: Optional[Callable] = None,
        inject: Optional[Callable] = None,
        kill_at: Optional[int] = None,
        profile: Optional[tracing.StepProfile] = None, print_fn=print):
    """Run ``step_fn`` from ``state`` with checkpoint/resume, fault
    injection and guarded recovery (module docstring).  ``comm`` is the
    run's `CommConfig` (checked on restore, read by the guard); ``key``
    is the PRNG key whose raw words a checkpoint stores and a resume
    must match.  Returns ``(state, losses)`` where ``losses`` covers
    the steps THIS call executed (a resumed call starts at the
    checkpoint step)."""
    plan = fault_plan or F.FaultPlan()
    if (plan or resume) and not ckpt_dir:
        raise ValueError("--fault/--resume need --ckpt-dir")
    if ckpt_dir:
        removed = ckpt.clean_orphans(ckpt_dir)
        if removed:
            print_fn(f"checkpoint: removed {len(removed)} orphaned "
                     f"tmp entr{'y' if len(removed) == 1 else 'ies'}")

    key_data = _key_data(key)
    save_tree = lambda st: {"state": st, "k_run": key_data}
    like = jax.eval_shape(save_tree, state)

    def save(step_done: int, tail: list):
        tree = save_tree(state)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(tree))
        with tracing.span("ckpt.save", bytes=nbytes):
            ckpt.save_state(
                ckpt_dir, tree, step=step_done, comm=comm,
                extra={"losses_tail": [float(x) for x in tail[-5:]],
                       "data_position": step_done}, keep=keep)

    start, loss_tail = 0, []
    if resume:
        with tracing.span("ckpt.restore"):
            tree, body = ckpt.restore_state(ckpt_dir, like, comm=comm)
        if not np.array_equal(np.asarray(tree["k_run"]), key_data):
            raise ckpt.CheckpointError(
                "checkpoint PRNG key != this run's seed — resuming "
                "would silently fork the trajectory")
        state, start = tree["state"], int(body["step"])
        loss_tail = list(body["extra"].get("losses_tail", []))
        print_fn(f"resumed from step {start} "
                 f"({ckpt.resolve_checkpoint(ckpt_dir)})")
    elif ckpt_dir and save_every:
        save(0, [])

    guard_state = bool(plan or (ckpt_dir and save_every))
    it = _skip_batches(dataset, batch_size, num_steps, start)
    it_pos = start
    fired = {s for s in plan.faults if s.step < start}
    losses, retries, step = [], 0, start
    try:
        while step < num_steps:
            if profile:
                profile.at(step)
            with tracing.step_span(step):
                if it_pos != step:
                    it = _skip_batches(dataset, batch_size, num_steps, step)
                    it_pos = step
                with tracing.span("feed"):
                    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
                it_pos += 1

                step_call = step_fn
                post_step = []
                for spec in plan.at(step):
                    if spec in fired:
                        continue
                    fired.add(spec)
                    print_fn(f"injecting fault {spec.text()}")
                    if spec.plane == "dp":
                        step_call = faulted_step(spec)
                    elif spec.plane == "bw":
                        # a corrupt backward hop lands in the params at the
                        # UPDATE — after the forward wrote clean messages —
                        # so bw injection follows the step (guard attribution
                        # depends on this timing; see faults.inject_sim_state)
                        post_step.append(spec)
                    else:
                        with tracing.span("fault", fault=spec.text()):
                            state = inject(state, spec)

                with tracing.span("dispatch"):
                    state, metrics = step_call(state, batch, step)
                for spec in post_step:
                    with tracing.span("fault", fault=spec.text()):
                        state = inject(state, spec)
                with tracing.span("sync"):
                    loss = float(metrics["loss"])
                try:
                    with tracing.span("guard"):
                        F.check_train_state(state if guard_state else {},
                                            comm=comm, step=step, loss=loss)
                except F.WireFaultError as e:
                    print_fn(f"guard tripped: {e}")
                    retries += 1
                    if not ckpt_dir or retries > max_retries:
                        raise
                    with tracing.span("ckpt.restore"):
                        tree, body = ckpt.restore_state(ckpt_dir, like,
                                                        comm=comm)
                    state, step = tree["state"], int(body["step"])
                    loss_tail = list(body["extra"].get("losses_tail", []))
                    losses = losses[:max(step - start, 0)]
                    print_fn(f"recovered from checkpoint step {step} "
                             f"(retry {retries}/{max_retries})")
                    continue

                losses.append(loss)
                loss_tail = (loss_tail + [loss])[-5:]
                if log_every and step % log_every == 0:
                    print_fn(_loss_line(step, loss))
                if kill_at is not None and step == kill_at:
                    print_fn(f"killing at step {step} (exit {KILL_EXIT_CODE})")
                    # simulate a hard preemption: no save, no cleanup, no
                    # python teardown — the next run must recover from the
                    # last committed checkpoint alone
                    os._exit(KILL_EXIT_CODE)
                step += 1
                if ckpt_dir and save_every and step % save_every == 0:
                    save(step, loss_tail)
    finally:
        if profile:
            profile.close()

    if ckpt_dir and save_every and num_steps % save_every != 0:
        save(num_steps, loss_tail)
    return state, losses


def run_sim_training(mcfg, tcfg, dataset, *, num_steps: int,
                     batch_size: int, log_every: int = 10,
                     ckpt_dir: str = "", save_every: int = 0,
                     keep: int = 3, resume: bool = False,
                     max_retries: int = 2,
                     fault_plan: Optional[F.FaultPlan] = None,
                     kill_at: Optional[int] = None, key=None,
                     profile: Optional[tracing.StepProfile] = None,
                     print_fn=print):
    """The simulated trainer through `run`.  Returns ``(state,
    losses)`` as `run` does.

    Math-identical to `training.simulated.train` — the same key
    discipline (``PRNGKey → split → (k_init, k_run)``, ``fold_in(k_run,
    step)`` per step), the same jitted step and static configs — so
    checkpointing off and an empty fault plan reproduce its loss stream
    bit-for-bit."""
    from repro.training import simulated as sim

    comm = tcfg.comm
    plan = fault_plan or F.FaultPlan()
    for spec in plan.faults:
        if spec.plane == "kv":
            raise ValueError("kv faults target the serving batcher "
                             "(launch.serve), not the trainer")
        if spec.plane == "dp" and not comm.dp.bits:
            raise ValueError(f"fault {spec.text()!r} needs "
                             f"--dp-grad-bits > 0")
        if spec.plane in ("fw", "zbuf") and comm.mode != "aqsgd":
            raise ValueError(f"fault {spec.text()!r} needs "
                             f"mode='aqsgd' (message buffers)")
        if spec.plane == "zbuf" and not comm.zbuf.bits:
            raise ValueError(f"fault {spec.text()!r} needs "
                             f"--buffer-bits > 0")

    key = key if key is not None else jax.random.PRNGKey(0)
    k_init, k_run = jax.random.split(key)
    state = sim.init_train_state(mcfg, tcfg, dataset.num_samples,
                                 dataset.dc.seq_len, k_init)

    def step_with(step_tcfg):
        return lambda st, batch, step: sim.train_step(
            st, batch, jax.random.fold_in(k_run, step), mcfg=mcfg,
            tcfg=step_tcfg)

    return run(
        step_with(tcfg), state, dataset, comm=comm, key=k_run,
        num_steps=num_steps,
        batch_size=batch_size, log_every=log_every, ckpt_dir=ckpt_dir,
        save_every=save_every, keep=keep, resume=resume,
        max_retries=max_retries, fault_plan=plan,
        faulted_step=lambda spec: step_with(dataclasses.replace(
            tcfg, comm=F.faulted_comm(comm, spec))),
        inject=lambda st, spec: F.inject_sim_state(st, spec, comm),
        kill_at=kill_at, profile=profile, print_fn=print_fn)
