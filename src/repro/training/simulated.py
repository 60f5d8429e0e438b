"""Bit-faithful single-process simulation of AQ-SGD pipeline training.

Mathematically identical to the K-machine distributed algorithm
(Algorithm 2): the model trunk is cut into K stages; at each of the K-1
boundaries the activation is replaced by the message m(ξ) (full precision
on first visit, += Q(Δ) afterwards) and the backward activation gradient
is quantized — exactly what the wire carries.  Because the simulation and
the distributed runtime share `core.aqsgd.apply_boundary`, convergence
results measured here transfer to the shard_map pipeline bit-for-bit
(up to collective reduction order).

This is the engine behind the paper-validation benchmarks (Fig. 1a/3/5/9).

The boundary codec backend (fused Pallas kernels vs reference jnp chain)
is selected by ``CompressionConfig.backend`` and flows through
``apply_boundary``/``read_buffers``/``write_buffers`` unchanged.  The two
backends are bit-identical per op (see core.boundary), so convergence
results measured here transfer across backends up to the usual
compiler-fusion ulp noise in the surrounding model compute.

All communication knobs live in ``SimTrainConfig.comm``
(`repro.comm.CommConfig`; the pre-registry flat kwargs now raise with
a migration message), and the DP wire is simulated by its registered
`WireSpec.sim_allreduce` from the wire registry.

DP gradient compression (Fig. 5, ``comm.dp.bits > 0``) uses the bucketed
error-feedback codec of `core.grad_compress`: each simulated worker's
gradient tree is flattened into one (rows, group_d) bucket, quantized
against the cross-worker shared scale through the fused boundary codec,
and accumulated as int32 codes — the identical math the shard_map
pipeline's `core.collectives.ef_psum_mean_bucket` wire executes, so this
simulation is bit-faithful to the distributed gradient wire (int32 code
sums are exact in any reduction order).

``dp_sharded=True`` simulates the ZeRO-sharded wire end-to-end: the
allreduce stops at the reduce-scatter midpoint
(`grad_compress.compress_reduce_scatter` — worker i keeps only its
owned segment's mean), AdamW runs in bucket space on segment owners
(`optim.adamw.apply_bucket_updates`, moments one segment per worker),
and the updated parameter bucket is reassembled — the same loop
`training/pipeline.py` runs under ``dp_wire="ring-sharded"``, here on
genuinely DISTINCT per-worker gradients.  Losses are bit-identical to
the ``dp_sharded=False`` path while trajectories coincide and track at
ulp level after (cross-program XLA fusion noise, not codec
divergence) — pinned by tests/test_grad_compress.py.
"""
from __future__ import annotations

import functools
from dataclasses import InitVar, dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from repro.comm import faults as faults_mod
from repro.comm.config import CommConfig, reject_legacy_comm
from repro.configs.base import ModelConfig
from repro.core import aqsgd
from repro.core import grad_compress
from repro.core.aqsgd import CompressionConfig
from repro.models import model as Mo
from repro.optim import adamw


@dataclass(frozen=True)
class SimTrainConfig:
    """Simulated-trainer knobs.  All communication lives in ``comm``
    (`repro.comm.CommConfig`); the DP plane's wire is simulated by its
    registered `WireSpec.sim_allreduce` (bit-faithful to the shard_map
    collective for the codec wires, math-faithful for passthroughs
    like ``fp16``).  The trailing init-only parameters are the REMOVED
    pre-registry kwargs (``compression=...``, ``dp_grad_bits=...``,
    ``dp_grad_group=...``, ``dp_sharded=...``) — kept only so passing
    one raises a loud migration error pointing at ``comm=``.  Read the
    old values off ``comm`` directly (``cfg.comm.dp.bits``,
    ``cfg.comm.activation``, ``cfg.comm.dp_wire_spec.sharded``, ...)."""
    num_stages: int = 4
    comm: Optional[CommConfig] = None
    optimizer: adamw.AdamWConfig = field(default_factory=adamw.AdamWConfig)
    dp_workers: int = 1             # simulated DP degree when dp bits > 0
    remat: bool = False
    # ---- REMOVED kwargs: raise with a migration message -----------------
    compression: InitVar[Optional[CompressionConfig]] = None
    dp_grad_bits: InitVar[Optional[int]] = None
    dp_grad_group: InitVar[Optional[int]] = None
    dp_sharded: InitVar[Optional[bool]] = None

    def __post_init__(self, compression, dp_grad_bits, dp_grad_group,
                      dp_sharded):
        reject_legacy_comm(
            "SimTrainConfig",
            {"compression": compression, "dp_grad_bits": dp_grad_bits,
             "dp_grad_group": dp_grad_group, "dp_sharded": dp_sharded})
        if self.comm is None:
            object.__setattr__(self, "comm", CommConfig())

    def with_comm(self, comm: CommConfig) -> "SimTrainConfig":
        """Copy with ``comm`` swapped (equivalent to
        ``dataclasses.replace``; kept because it predates the removal
        of the legacy mirror kwargs)."""
        import dataclasses as _dc
        return _dc.replace(self, comm=comm)


def init_train_state(mcfg: ModelConfig, tcfg: SimTrainConfig,
                     num_samples: int, seq_len: int, key) -> dict:
    params = Mo.init_params(mcfg, key)
    dpc = tcfg.comm.dp
    if dpc.bits and tcfg.comm.dp_wire_spec.sharded:
        # ZeRO sim: segment-partitioned bucket moments, one per worker
        lay = grad_compress.bucket_layout(params, dpc.group_d)
        seg = grad_compress.ring_segment_rows(lay.rows,
                                              tcfg.dp_workers)
        opt = adamw.init_bucket_opt_state(tcfg.dp_workers, seg,
                                          lay.group_d)
    else:
        opt = adamw.init_opt_state(params)
    state = {
        "params": params,
        "opt": opt,
        "buffers": aqsgd.init_buffers(
            tcfg.comm.activation, tcfg.num_stages - 1, num_samples,
            seq_len, mcfg.d_model),
    }
    if dpc.bits:
        err = grad_compress.init_error_state(params, dpc.group_d)
        state["dp_error"] = jnp.stack([err] * tcfg.dp_workers)
    return state


def _loss_with_boundaries(params, mcfg, tcfg, batch, m_all, seen_all, key):
    cc = tcfg.comm.activation
    nb = tcfg.num_stages - 1

    def boundary_fn(bstate, h, idx):
        kb = jax.random.fold_in(key, idx)
        m = m_all[idx] if m_all is not None else None
        seen = seen_all[idx] if seen_all is not None else None
        h2, m_new = aqsgd.apply_boundary(cc, h, kb, m, seen)
        return bstate + (m_new,), h2

    loss, metrics = Mo.loss_fn(
        params, mcfg, batch, num_stages=tcfg.num_stages,
        boundary_fn=boundary_fn, boundary_state=(), remat=tcfg.remat)
    return loss, metrics


@functools.partial(jax.jit, static_argnames=("mcfg", "tcfg"),
                   donate_argnames=("state",))
def train_step(state, batch, key, *, mcfg: ModelConfig,
               tcfg: SimTrainConfig):
    """One AQ-SGD training step.  batch must include sample_ids.

    ``state`` is donated: the step writes the new state into its
    buffers (the message store in place), and the caller's ``state``
    is deleted, so rebind it to the result."""
    cc = tcfg.comm.activation
    dpc = tcfg.comm.dp
    dp_spec = tcfg.comm.dp_wire_spec if dpc.bits else None
    dp_sharded = bool(dp_spec is not None and dp_spec.sharded)
    bufs = state["buffers"]
    ids = batch["sample_ids"]
    if cc.mode == "aqsgd":
        m_all, seen_all = aqsgd.read_buffers(cc, bufs, ids, mcfg.d_model)
    else:
        m_all = seen_all = None

    grad_fn = jax.value_and_grad(
        lambda p: _loss_with_boundaries(p, mcfg, tcfg, batch, m_all,
                                        seen_all, key), has_aux=True)

    if dpc.bits and (tcfg.dp_workers > 1 or dp_sharded):
        # Fig. 5 mode: split the batch over simulated DP workers, then
        # run the configured wire's registered simulator
        # (`WireSpec.sim_allreduce`) over the per-worker gradient trees
        # — bit-faithful to the shard_map collective for the codec
        # wires (psum/ring/ring-sharded), math-faithful for
        # passthroughs like fp16 (f16 sums are order-dependent).
        w = tcfg.dp_workers
        b = batch["tokens"].shape[0] // w
        glist, loss = [], 0.0
        new_ms_parts, ce = [], 0.0
        for i in range(w):
            sub = {k: v[i * b:(i + 1) * b] for k, v in batch.items()}
            sub_m = m_all[:, i * b:(i + 1) * b] \
                if m_all is not None else None
            sub_s = seen_all[:, i * b:(i + 1) * b] \
                if seen_all is not None else None
            (l, met), g = jax.value_and_grad(
                lambda p: _loss_with_boundaries(
                    p, mcfg, tcfg, sub, sub_m, sub_s,
                    jax.random.fold_in(key, 1000 + i)), has_aux=True)(
                        state["params"])
            glist.append(g)
            loss = loss + l / w
            ce = ce + met["ce"] / w
            new_ms_parts.append(met["boundary_state"])
        glay = grad_compress.bucket_layout(glist[0], dpc.group_d)
        # sharded wires stop at the reduce-scatter midpoint — worker i
        # keeps only its owned segment's mean; the bucket-space
        # optimizer below updates owned segments and reassembles.
        err_in = state["dp_error"] if dpc.error_feedback \
            else jnp.zeros_like(state["dp_error"])
        grads, new_err = dp_spec.sim_allreduce(
            glist, err_in, dpc.bits,
            jax.random.fold_in(key, 2000), stochastic=dpc.stochastic,
            backend=dpc.backend, layout=glay)
        # payload guard: NaN-poison a corrupt decoded mean (and the EF
        # carry, so the fault is attributable to the dp plane); clean
        # payloads pass through bit-exactly
        grads, new_err = faults_mod.guard_dp_pair(grads, new_err)
        if not dpc.error_feedback:
            new_err = jnp.zeros_like(new_err)
        new_state_extra = {"dp_error": new_err}
        if cc.mode == "aqsgd":
            # workers own disjoint batch shards; concat their new messages
            nb = tcfg.num_stages - 1
            bstate = tuple(
                jnp.concatenate([new_ms_parts[i][j] for i in range(w)],
                                axis=0) for j in range(nb))
        else:
            bstate = ()
        metrics = {"ce": ce, "aux": 0.0, "boundary_state": bstate}
    elif dpc.bits:
        # single-worker error feedback: the n=1 wire through the same
        # registered simulator (bit-identical to the old
        # `compress_gradients` path for the codec wires: the n=1 code
        # sum decodes through the identical `decode_sum_mean`).
        (loss, metrics), grads = grad_fn(state["params"])
        err_in = state["dp_error"] if dpc.error_feedback \
            else jnp.zeros_like(state["dp_error"])
        grads, new_err = dp_spec.sim_allreduce(
            [grads], err_in, dpc.bits,
            jax.random.fold_in(key, 2000), stochastic=dpc.stochastic,
            backend=dpc.backend,
            layout=grad_compress.bucket_layout(grads, dpc.group_d))
        grads, new_err = faults_mod.guard_dp_pair(grads, new_err)
        if not dpc.error_feedback:
            new_err = jnp.zeros_like(new_err)
        new_state_extra = {"dp_error": new_err}
    else:
        (loss, metrics), grads = grad_fn(state["params"])
        new_state_extra = {}

    if dpc.bits and dp_sharded:
        # segment-owner update in bucket space + parameter reassembly
        # (the sim analogue of the pipeline's parameter all-gather):
        # bit-identical losses to the allreduce + per-leaf AdamW path
        w = tcfg.dp_workers
        lay = grad_compress.bucket_layout(state["params"], dpc.group_d)
        seg = grad_compress.ring_segment_rows(lay.rows, w)
        pb = grad_compress.flatten_bucket(state["params"], lay)
        pad = seg * w - lay.rows
        if pad:
            pb = jnp.pad(pb, ((0, pad), (0, 0)))
        new_pb, opt = adamw.apply_bucket_updates(
            tcfg.optimizer, pb.reshape(w, seg, lay.group_d), grads,
            state["opt"])
        params = grad_compress.unflatten_bucket(
            new_pb.reshape(w * seg, lay.group_d)[:lay.rows], lay,
            state["params"])
    else:
        params, opt = adamw.apply_updates(
            tcfg.optimizer, state["params"], grads, state["opt"])

    if cc.mode == "aqsgd":
        bufs = aqsgd.write_buffers(cc, bufs, ids,
                                   jnp.stack(metrics.pop("boundary_state")))
    else:
        metrics.pop("boundary_state", None)

    new_state = {"params": params, "opt": opt, "buffers": bufs,
                 **new_state_extra}
    metrics = {"loss": loss, "ce": metrics["ce"], "aux": metrics["aux"]}
    return new_state, metrics


def train(mcfg: ModelConfig, tcfg: SimTrainConfig, dataset, *,
          num_steps: int, batch_size: int, key=None, log_every: int = 0,
          initial_params=None):
    """Run the simulated trainer; returns (state, list of per-step loss).

    initial_params: start from a pre-trained checkpoint (the paper's
    fine-tuning setting) instead of random init."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k_init, k_run = jax.random.split(key)
    state = init_train_state(mcfg, tcfg, dataset.num_samples,
                             dataset.dc.seq_len, k_init)
    if initial_params is not None:
        # a copy: the donating step would delete the caller's arrays
        state["params"] = jax.tree.map(jnp.copy, initial_params)
    losses = []
    for step, batch in enumerate(dataset.batches(batch_size, num_steps)):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, metrics = train_step(state, batch,
                                    jax.random.fold_in(k_run, step),
                                    mcfg=mcfg, tcfg=tcfg)
        losses.append(float(metrics["loss"]))
        if log_every and step % log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f}")
    return state, losses
