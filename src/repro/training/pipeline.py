"""Distributed pipeline-parallel training with AQ-SGD boundary compression.

Mesh: ``(data=D, model=K)`` (+ leading ``pod`` for multi-pod).  The
``model`` axis carries the K pipeline stages — the paper's setting (its
experiments cut the model onto 8 machines; the production mesh uses 16).
The ``data``/``pod`` axes carry data parallelism with per-layer ZeRO-3
weight gathering (stage weights of e.g. mixtral-8x22b do not fit one chip).

Schedule: GPipe with M microbatches as a ``lax.scan`` over T = M + K - 1
ticks inside ``shard_map``.  Each tick every stage computes its current
microbatch and ships the boundary activation to the next stage with
``ppermute``.  Autodiff of the scan yields the reverse (backward)
pipeline automatically; the boundary transfer is a ``custom_vjp`` so that

* forward wire  = packed uint8 delta codes + per-row scales (AQ-SGD), and
* backward wire = packed uint8 gradient codes + scales (bw-bit DirectQ),

i.e. the lowered ``collective-permute`` ops genuinely carry 2-8 bit
payloads — the compression shows up in the §Roofline collective term.

All communication knobs live in ``PipelineConfig.comm``
(`repro.comm.CommConfig`: fw / bw / z-buffer / dp planes; the old flat
kwargs now raise with a migration message), and the DP collective is resolved
by name from the wire registry (`repro.comm.wires`), so a newly
registered wire reaches this trainer with no changes here.

DP gradient wire (``comm.dp.bits > 0``, paper Fig. 5 "end-to-end
communication compression"): the whole gradient tree is flattened into
one bucketed (rows, group_d) array and allreduced over the DP axes —
pmax-shared rowwise scales, fused codes-only quantize, exact int32 code
accumulation, fused dequant-mean — with per-rank error-feedback state
(``dp_error`` in the train state, sharded one bucket per DP rank).
``comm.dp.wire`` picks the collective: the bandwidth-optimal compressed ring
(packed b-bit codes on ``ppermute`` hops, local unpack-accumulate —
the default), the conservative i32-lane code ``psum``, or the
ZeRO-sharded ``ring-sharded`` (the ring stopped at its reduce-scatter
midpoint: each rank keeps only its owned segment's mean, AdamW runs in
bucket space on segment owners — `adamw.apply_bucket_updates` with
moments partitioned one segment per rank — and the f32 UPDATED
parameter segments all-gather explicitly inside
`make_dp_sharded_update`, the gather ZeRO trades for the gradient
all-gather); all three produce
bit-identical gradient values (see `make_dp_grad_wire` /
`make_dp_sharded_update`).  The wire FUNCTIONS are
bit-identical to the simulator's `grad_compress.compress_allreduce` /
`compress_reduce_scatter` (tests/workers/dp_grad_worker.py feeds them
DISTINCT per-rank buckets — the local-gradient regime — and compares
bit-for-bit, so the wires, the error-feedback layout, and the sharded
optimizer state are all proven on per-rank partial gradients; the
simulator's ``dp_sharded`` mode runs that full ZeRO loop on genuinely
distinct per-worker gradients).  Placement caveat: in THIS train step
the bucket each rank feeds in is the gradient `jax.value_and_grad`
already produced at the pjit level — which includes XLA's fp32
cross-data reduction — so the collective performs n independent
stochastic quantizations of the shared gradient with per-rank error
feedback (the pure-DP / pod-axis semantics).  That placement is what
keeps all three wires loss-identical end-to-end; feeding the pipeline
wire from pre-reduction local cotangents (a custom_vjp on
`gather_fsdp` / a shard_map'd per-rank loss) remains a ROADMAP item.

Message buffers: each device holds ``m_out`` (its outgoing boundary) and
``m_in`` (a replica of the upstream stage's buffer).  Both sides apply
the *same* quantized delta so they stay bit-identical (Algorithm 2).  The
first epoch runs the ``warmup=True`` step variant: uncompressed transfer
that initializes the buffers (the paper's warm-up epoch).

Stage homogeneity: layer stacks are zero-padded to K*lps and dead layers
are skipped with ``lax.cond`` (counted in §Roofline's useful-FLOPs
ratio); zamba2's shared attention block is invoked by per-layer flag,
also under ``lax.cond``.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import InitVar, dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import faults as CF
from repro.comm import wires as CW
from repro.comm.config import CommConfig, reject_legacy_comm
from repro.configs.base import ModelConfig
from repro.core import boundary as B
from repro.core import collectives as C
from repro.core import grad_compress as GC
from repro.core import quantization as Q
from repro.core.aqsgd import CompressionConfig
from repro.launch.mesh import data_axes, shard_map
from repro.models import layers as L
from repro.models import model as Mo
from repro.models import moe as Me
from repro.models import ssm as S
from repro.optim import adamw


@dataclass(frozen=True)
class PipelineConfig:
    """Pipeline-trainer knobs.  All communication lives in ``comm``
    (`repro.comm.CommConfig`: fw / bw / z-buffer / dp planes, wire
    names from the registry); ``comm=None`` means the default
    `CommConfig()`.  The trailing init-only parameters are the REMOVED
    pre-registry kwargs (``compression=...``, ``buffer_bits=...``,
    ``dp_grad_bits=...``, ``dp_grad_group=...``, ``dp_wire=...``) —
    kept only so passing one raises a loud migration error pointing at
    ``comm=`` instead of an opaque TypeError.  Read the old values off
    ``comm`` directly (``cfg.comm.dp.bits``, ``cfg.comm.activation``,
    ...); ``dataclasses.replace(cfg, comm=new)`` and ``with_comm``
    both swap comm."""
    microbatches: int = 16
    comm: Optional[CommConfig] = None
    warmup: bool = False            # warm-up epoch: uncompressed, fills m
    remat: bool = True
    block_k: int = 512
    buffer_dtype: str = "bfloat16"  # HBM-resident message buffer precision
    loss_chunks: int = 64           # sequential CE chunks (bounds logits mem)
    moe_mode: str = "zero3"         # zero3 | expert_parallel (§Perf)
    remat_mode: str = "nested"      # nested | layer (§Perf: nested saves
                                    # HBM, layer saves one fwd recompute)
    # ---- REMOVED kwargs: raise with a migration message -----------------
    compression: InitVar[Optional[CompressionConfig]] = None
    buffer_bits: InitVar[Optional[int]] = None
    dp_grad_bits: InitVar[Optional[int]] = None
    dp_grad_group: InitVar[Optional[int]] = None
    dp_wire: InitVar[Optional[str]] = None

    def __post_init__(self, compression, buffer_bits, dp_grad_bits,
                      dp_grad_group, dp_wire):
        reject_legacy_comm(
            "PipelineConfig",
            {"compression": compression, "buffer_bits": buffer_bits,
             "dp_grad_bits": dp_grad_bits,
             "dp_grad_group": dp_grad_group, "dp_wire": dp_wire})
        if self.comm is None:
            object.__setattr__(self, "comm", CommConfig())

    def with_comm(self, comm: CommConfig) -> "PipelineConfig":
        """Copy of this config with ``comm`` swapped (equivalent to
        ``dataclasses.replace(self, comm=comm)``; kept because it
        predates the removal of the legacy mirror kwargs)."""
        return dataclasses.replace(self, comm=comm)


# ---------------------------------------------------------------------------
# stage layout: pad layers to K * lps, per-layer flags
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StageLayout:
    num_stages: int
    lps: int                         # layers per stage (padded)
    n_layers: int                    # live layers in the pipeline trunk
    n_padded: int
    shared_attn: bool                # zamba2


def stage_layout(cfg: ModelConfig, num_stages: int) -> StageLayout:
    n = cfg.num_layers - cfg.first_dense_layers
    lps = -(-n // num_stages)
    return StageLayout(num_stages, lps, n, num_stages * lps - n,
                       cfg.family == "hybrid")


def pad_stack(tree, n_pad: int):
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda a: jnp.pad(a, [(0, n_pad)] + [(0, 0)] * (a.ndim - 1)), tree)


def to_pipeline_params(cfg: ModelConfig, params, num_stages: int):
    """Canonical init_params -> pipeline layout (stage-stacked trunk)."""
    lay = stage_layout(cfg, num_stages)
    p = dict(params)
    trunk = pad_stack(p.pop("layers"), lay.n_padded)
    p["stages"] = jax.tree.map(
        lambda a: a.reshape(num_stages, lay.lps, *a.shape[1:]), trunk)
    return p


def from_pipeline_params(cfg: ModelConfig, params, num_stages: int):
    lay = stage_layout(cfg, num_stages)
    p = dict(params)
    stages = p.pop("stages")
    trunk = jax.tree.map(
        lambda a: a.reshape(num_stages * lay.lps, *a.shape[2:])[:lay.n_layers],
        stages)
    p["layers"] = trunk
    return p


def layer_flags(cfg: ModelConfig, lay: StageLayout, seq_len: int):
    """Per padded-layer vectors: window, live mask, shared-attn flag."""
    n, total = lay.n_layers, lay.num_stages * lay.lps
    off = cfg.first_dense_layers
    windows = np.array(
        [cfg.layer_window(i + off, seq_len) for i in range(n)]
        + [seq_len] * lay.n_padded, np.int32)
    live = np.array([True] * n + [False] * lay.n_padded)
    shared = np.array(
        [cfg.layer_has_shared_attn(i) for i in range(n)]
        + [False] * lay.n_padded)
    return (jnp.asarray(windows).reshape(lay.num_stages, lay.lps),
            jnp.asarray(live).reshape(lay.num_stages, lay.lps),
            jnp.asarray(shared).reshape(lay.num_stages, lay.lps))


# ---------------------------------------------------------------------------
# FSDP (ZeRO-3) sharding of stage-stacked params over the data axis
# ---------------------------------------------------------------------------

def fsdp_dim(shape, dsize: int, skip: int) -> Optional[int]:
    """Dim (>= skip) to shard over data: first trailing dim divisible."""
    for i in range(skip, len(shape)):
        if shape[i] % dsize == 0 and shape[i] >= dsize:
            return i
    return None


def pipeline_param_specs(mesh, params_shape) -> Any:
    """Shardings for pipeline-layout params.

    stages/* leaves: (K, lps, ...) -> P('model', None, fsdp...).
    everything else (embed/head/prefix/shared_block/...): fsdp over data,
    last dim over model when divisible.  FSDP uses the intra-pod 'data'
    axis only — params replicate across pods (the pod axis is pure DP,
    which is where the paper's DP gradient compression applies).
    """
    dsize = mesh.shape["data"]

    def stage_rule(leaf):
        spec = [None] * leaf.ndim
        spec[0] = "model"
        fd = _stage_fsdp_dim(leaf, dsize)
        if fd is not None:
            spec[fd] = "data"
        return NamedSharding(mesh, P(*spec))

    def other_rule(leaf):
        spec = [None] * leaf.ndim
        fd = fsdp_dim(leaf.shape, dsize, 0)
        if fd is not None:
            spec[fd] = "data"
        msz = mesh.shape["model"]
        if leaf.ndim >= 2 and spec[-1] is None and \
                leaf.shape[-1] % msz == 0 and fd != leaf.ndim - 1:
            spec[-1] = "model"
        return NamedSharding(mesh, P(*spec))

    out = {}
    for k, v in params_shape.items():
        out[k] = jax.tree.map(stage_rule if k == "stages" else other_rule, v)
    return out


def _is_expert_leaf(leaf, stage_leaf: bool) -> bool:
    """MoE expert stacks are the only 5-D stage leaves (K, lps, E, d, ff).
    They get skip=3 (never shard the expert dim in the baseline) and are
    gathered per-expert inside the MoE scan, not per-layer."""
    return stage_leaf and leaf.ndim >= 5


def _stage_fsdp_dim(leaf, dsize: int):
    return fsdp_dim(leaf.shape, dsize, 3 if _is_expert_leaf(leaf, True)
                    else 2)


def fsdp_dims_tree(tree_shape, dsize: int, skip: int, shift: int = 0,
                   stage: bool = False):
    """Static pytree of Optional[int]: which dim of each leaf is
    FSDP-sharded over `data` (computed on GLOBAL shapes; `shift` adjusts
    indices for dims squeezed/scanned away inside shard_map).  Expert
    leaves are marked -1 here (gathered per-expert, see expert_axes)."""
    def rule(leaf):
        if _is_expert_leaf(leaf, stage):
            return -1
        fd = fsdp_dim(leaf.shape, dsize, skip)
        return -1 if fd is None else fd - shift
    return jax.tree.map(rule, tree_shape)


def expert_axes(stages_shape, dsize: int) -> dict:
    """{leaf name: gather axis of a single expert's weight inside the
    MoE expert scan} for the 5-D expert leaves.  Global (K, lps, E, d,
    ff) with fsdp dim fd -> per-expert local axis fd - 3."""
    axes = {}
    ffn = stages_shape.get("ffn", {}) if isinstance(stages_shape, dict) \
        else {}
    for name in ("w_gate", "w_up", "w_down"):
        leaf = ffn.get(name)
        if leaf is not None and leaf.ndim >= 5:
            fd = _stage_fsdp_dim(leaf, dsize)
            axes[name] = -1 if fd is None else fd - 3
    return axes


def gather_fsdp(tree, dims_tree):
    """Per-leaf all-gather over 'data' at the recorded dim (ZeRO-3)."""
    def g(leaf, fd):
        if fd < 0:
            return leaf
        return jax.lax.all_gather(leaf, "data", axis=fd, tiled=True)
    return jax.tree.map(g, tree, dims_tree)


# ---------------------------------------------------------------------------
# boundary transfer (compressed ppermute with custom_vjp)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def make_transfer(mode: str, fw_bits: int, bw_bits: int, stochastic: bool,
                  num_stages: int, axis: str = "model",
                  backend: str = "reference"):
    """Returns transfer(out, m_out_s, m_in_s, key) ->
    (recv, new_m_out_s, new_m_in_s); all (mb, S, d) floats.

    mode: 'fp32' | 'warmup' | 'directq' | 'aqsgd'.  backend selects the
    boundary codec (`repro.core.boundary`): the ppermute ships exactly
    the packed uint8 codes + f32 scales the fused kernel emits — nothing
    is re-packed on the wire path."""
    if mode in ("directq", "aqsgd"):
        # the real wire requires dense byte-aligned packing; fw3/bw6
        # ablation widths are simulation-only (training/simulated.py)
        assert fw_bits in B.PACKABLE_BITS, \
            f"wire fw_bits must be one of {B.PACKABLE_BITS}, got {fw_bits}"
        assert bw_bits >= 32 or bw_bits in B.PACKABLE_BITS, \
            f"wire bw_bits must be one of {B.PACKABLE_BITS}, got {bw_bits}"
    fwd_perm = tuple((i, (i + 1) % num_stages) for i in range(num_stages))
    bwd_perm = tuple((j, i) for i, j in fwd_perm)

    def pp(x, perm):
        return jax.lax.ppermute(x, axis, perm)

    def _fwd(out, m_out_s, m_in_s, key):
        d = out.shape[-1]
        if mode in ("fp32", "warmup"):
            recv = pp(out, fwd_perm)
            if mode == "warmup":
                new_m_out, new_m_in = out, recv
            else:
                new_m_out, new_m_in = m_out_s, m_in_s
        elif mode == "directq":
            packed, scale = B.encode(out, bits=fw_bits,
                                     stochastic=stochastic, key=key,
                                     backend=backend)
            packed, scale = pp(packed, fwd_perm), pp(scale, fwd_perm)
            recv = B.decode(packed, scale, bits=fw_bits, d=d,
                            dtype=out.dtype, backend=backend)
            new_m_out, new_m_in = m_out_s, m_in_s
        elif mode == "aqsgd":
            packed, scale, nmo = B.encode_delta(
                out, m_out_s, bits=fw_bits, stochastic=stochastic,
                key=key, backend=backend)
            new_m_out = nmo.astype(m_out_s.dtype)
            packed, scale = pp(packed, fwd_perm), pp(scale, fwd_perm)
            new_m_in = B.decode_accumulate(
                packed, scale, m_in_s, bits=fw_bits,
                backend=backend).astype(m_in_s.dtype)
            recv = new_m_in.astype(out.dtype)
        else:
            raise ValueError(mode)
        return recv, new_m_out, new_m_in

    @jax.custom_vjp
    def transfer(out, m_out_s, m_in_s, key):
        return _fwd(out, m_out_s, m_in_s, key)

    def transfer_fwd(out, m_out_s, m_in_s, key):
        outs = _fwd(out, m_out_s, m_in_s, key)
        zeros = (jnp.zeros((), m_out_s.dtype), jnp.zeros((), m_in_s.dtype))
        return outs, (key, zeros)

    def transfer_bwd(res, gs):
        key, (zo, zi) = res
        mo_dt, mi_dt = zo.dtype, zi.dtype
        g = gs[0]                      # buffer cotangents are discarded:
        d = g.shape[-1]                # messages are not differentiated
        if mode in ("fp32", "warmup") or bw_bits >= 32:
            gout = pp(g, bwd_perm)
        else:
            kb = jax.random.fold_in(key, 7)
            packed, scale = B.encode(g, bits=bw_bits,
                                     stochastic=stochastic, key=kb,
                                     backend=backend)
            packed, scale = pp(packed, bwd_perm), pp(scale, bwd_perm)
            gout = B.decode(packed, scale, bits=bw_bits, d=d,
                            dtype=g.dtype, backend=backend)
        zero = np.zeros(key.shape, jax.dtypes.float0)
        return (gout, jnp.zeros(g.shape, mo_dt), jnp.zeros(g.shape, mi_dt),
                zero)

    transfer.defvjp(transfer_fwd, transfer_bwd)
    return transfer


# ---------------------------------------------------------------------------
# DP gradient wire (error-feedback compressed allreduce, paper Fig. 5)
# ---------------------------------------------------------------------------

def replicate_leaves(mesh, tree):
    """Pin every leaf of `tree` to a fully-replicated sharding.

    GSPMD workaround (jax 0.4.x, meshes with a model axis):
    ``jnp.concatenate`` of differently-sharded flattened leaves — the
    exact shape of `grad_compress.flatten_bucket` on the gradient or
    parameter tree — miscompiles and DOUBLES the values of multi-axis
    sharded leaves (the partitioner treats the replicas it gathers as
    partial sums).  Constraining each leaf replicated before the
    reshape+concat forces a plain all-gather first, which is what the
    wire's P(None, None) bucket input needs anyway.  The ring-sharded
    loss-parity worker (tests/workers/pipeline_worker.py
    ``check_dp_wire_parity``) regresses this: without the constraint
    the DP bucket ships 2x gradients on any mesh with model > 1."""
    def rep(leaf):
        spec = P(*([None] * leaf.ndim))
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))
    return jax.tree.map(rep, tree)


def make_dp_grad_wire(mesh, comm: CommConfig):
    """shard_map'd compressed gradient allreduce over the DP axes.

    The gradient tree is flattened into one (rows, group_d) bucket
    (`core.grad_compress.bucket_layout`) which every device holds in
    full.  ``comm.dp.wire`` names the collective in the wire registry
    (`repro.comm.wires` — ``--list-wires`` prints the table); any
    registered full-mean DP wire flows through here with NO trainer
    changes — that is the point of the registry (the ``fp16``
    passthrough is the in-tree example).  The built-in codec wires
    (``ring``/``psum``) pmax-share the rowwise scale, quantize through
    the fused boundary codec, and accumulate int32 codes, so they
    produce BIT-IDENTICAL results and the switch is purely a wire-cost
    choice (see each `core.collectives` docstring).

    Error-feedback state is per DP rank: a (D, rows, group_d) array
    sharded over the data axes so each device carries exactly its own
    feedback bucket (``comm.dp.error_feedback=False`` zeroes the carry
    — plain one-shot quantization; the state slot stays for layout
    stability).

    Noise keys fold in the device's DP position, so ranks draw
    independent rounding noise and the allreduce is a genuine n-worker
    compressed mean — bit-identical to the wire's registered simulator
    (`WireSpec.sim_allreduce`) with the same base key and the same
    per-rank inputs, where the wire claims bit parity at all.  (In
    `make_train_step` the input bucket is the pjit-level gradient,
    already reduced over data by autodiff — see the module docstring's
    placement caveat.)"""
    daxes = data_axes(mesh)
    axis = daxes if len(daxes) > 1 else daxes[0]
    dpc = comm.dp
    # sharded wires have no standalone mean-producing form at this
    # level: their segment mean must stay inside the shard_map that
    # consumes it (`make_dp_sharded_update`), so this factory only
    # serves the full-mean wires.
    spec = CW.get_wire(dpc.wire, plane="dp-grad")
    assert spec.collective is not None and not spec.sharded, dpc.wire
    # chunkable wires take the K-chunk double-buffered schedule knob;
    # CommConfig already validated chunks against the registry flag
    extra = {"chunks": dpc.chunks} if spec.chunkable else {}

    def wire(g2d, err, key):
        e = err[0] if dpc.error_feedback else jnp.zeros_like(err[0])
        mean, new_err = spec.collective(
            g2d, e, axis, dpc.bits, key,
            stochastic=dpc.stochastic, backend=dpc.backend, **extra)
        # payload guard (repro.comm.faults): NaN-poison a corrupt or
        # dropped-hop decoded mean; bit-exact passthrough when clean
        mean, new_err = CF.guard_dp_pair(mean, new_err)
        if not dpc.error_feedback:
            new_err = jnp.zeros_like(new_err)
        return mean, new_err[None]

    return shard_map(wire, mesh,
                     (P(None, None), P(axis, None, None), P()),
                     (P(None, None), P(axis, None, None)))


def make_dp_sharded_update(mesh, comm: CommConfig,
                           opt_cfg: adamw.AdamWConfig, glayout):
    """The fused ZeRO step for ``dp_wire="ring-sharded"``: compressed
    reduce-scatter + segment-owner AdamW + parameter all-gather, all
    inside ONE shard_map over the DP axes.

    Per DP rank: ship the packed b-bit codes of every segment to its
    owner (`C.ring_ef_reduce_scatter_bucket`), decode only the owned
    segment's mean, update the owned (seg, group_d) slices of the
    parameter bucket and the AdamW moments
    (`adamw.apply_bucket_updates` — moments never exist unsharded),
    then ``all_gather`` the UPDATED f32 parameter segments so every
    rank leaves with the full new bucket.  That gather is the ZeRO
    parameter all-gather that replaces the gradient all-gather — it is
    an explicit collective here (visible to `launch/hlo_cost`), and the
    full-bucket output is genuinely replicated on every device, so the
    pjit-level unflatten consumes a clean P(None, None) array exactly
    like the full ring's mean.  (Keeping the segment mean INSIDE the
    shard_map matters: handing a data-sharded, model-unmentioned wire
    output back to GSPMD for the optimizer arithmetic lets the
    partitioner introduce cross-model reductions of values it believes
    are partial — the bit-parity worker caught exactly that.)

    Returns update(bucket, dp_error, pbucket, mu, nu, step, key) ->
    (new full bucket (rows, group_d), new dp_error, new mu, new nu,
    new step); pbucket/mu/nu are (n_ranks, seg, group_d) stacks sharded
    one segment per rank.  The collective comes from the wire registry
    (``comm.dp.wire`` must name a ``sharded=True`` spec)."""
    daxes = data_axes(mesh)
    axis = daxes if len(daxes) > 1 else daxes[0]
    rows = glayout.rows
    dpc = comm.dp
    spec = CW.get_wire(dpc.wire, plane="dp-grad")
    assert spec.sharded and spec.collective is not None, dpc.wire
    extra = {"chunks": dpc.chunks} if spec.chunkable else {}

    def upd(g2d, err, pb, mu, nu, step, key):
        e = err[0] if dpc.error_feedback else jnp.zeros_like(err[0])
        seg_mean, new_err = spec.collective(
            g2d, e, axis, dpc.bits, key,
            stochastic=dpc.stochastic, backend=dpc.backend, **extra)
        # expect_nonzero off: a small model can leave this rank's
        # segment entirely padding rows (legitimately all-zero)
        seg_mean, new_err = CF.guard_dp_pair(seg_mean, new_err,
                                             expect_nonzero=False)
        if not dpc.error_feedback:
            new_err = jnp.zeros_like(new_err)
        new_pseg, new_opt = adamw.apply_bucket_updates(
            opt_cfg, pb[0], seg_mean,
            {"mu": mu[0], "nu": nu[0], "step": step})
        full = jax.lax.all_gather(new_pseg, axis, axis=0,
                                  tiled=True)[:rows]
        return (full, new_err[None], new_opt["mu"][None],
                new_opt["nu"][None], new_opt["step"])

    seg_spec = P(axis, None, None)
    return shard_map(upd, mesh,
                     (P(None, None), seg_spec, seg_spec, seg_spec,
                      seg_spec, P(), P()),
                     (P(None, None), seg_spec, seg_spec, seg_spec, P()))


def init_dp_error(pcfg: "PipelineConfig", params, n_ranks: int):
    """Initial per-rank error-feedback stack (n_ranks, rows, group_d) —
    the one place that ties the stack depth to the mesh's DP product and
    the bucket width to `pcfg.comm.dp.group_d`, so callers cannot drift
    from the layout `make_train_step` traces against.
    (`make_state_structs` derives its dp_error struct by eval_shape of
    THIS function, and tests/test_grad_compress.py pins the layout on
    every mesh the workers exercise.)

    The error stays full-bucket per rank under EVERY wire, including
    ``ring-sharded``: each rank encodes its whole compensated bucket
    (it ships every segment to that segment's owner), so only the
    *reduced gradient* and the optimizer state are segment-sharded."""
    err = GC.init_error_state(params, pcfg.comm.dp_group_d)
    return jnp.stack([err] * n_ranks)


def dp_bucket_segment(pcfg: "PipelineConfig", params, n_ranks: int) -> int:
    """Segment rows of the ZeRO-sharded gradient bucket: the single
    source for the (n_ranks, seg, group_d) layout shared by the wire
    output, `adamw.init_bucket_opt_state`, and the pjit sharding
    specs."""
    lay = GC.bucket_layout(params, pcfg.comm.dp_group_d)
    return C.ring_segment_rows(lay.rows, n_ranks)


def init_sharded_opt(pcfg: "PipelineConfig", params, n_ranks: int) -> dict:
    """Segment-partitioned AdamW state for ``dp_wire="ring-sharded"``:
    (n_ranks, seg, group_d) moment buckets, one owned segment per DP
    rank (placed P(data-axes) by `make_train_step`'s state specs).
    Replaces `adamw.init_opt_state`'s per-leaf tree in sharded mode."""
    seg = dp_bucket_segment(pcfg, params, n_ranks)
    return adamw.init_bucket_opt_state(n_ranks, seg,
                                       pcfg.comm.dp_group_d)


# ---------------------------------------------------------------------------
# message-buffer codec (z-bit storage, paper §H.5)
# ---------------------------------------------------------------------------

def buffer_read(pcfg: PipelineConfig, buf, ids):
    """buf slice for a microbatch -> f32 (mb, S, d).

    Messages are never differentiated (the transfer custom_vjp discards
    their cotangents), so the codec runs under stop_gradient — which also
    keeps the fused pallas decode out of the autodiff trace."""
    zb = pcfg.comm.zbuf
    if zb.bits:
        codes = jax.lax.stop_gradient(buf["codes"][ids])
        scale = jax.lax.stop_gradient(buf["scale"][ids])
        d = buf["codes"].shape[-1] * Q.codes_per_byte(zb.bits)
        return zb.codec().decode(codes, scale, d=d)
    return buf[ids].astype(jnp.float32)


def buffer_write(pcfg: PipelineConfig, buf, ids, val, keep_mask):
    """Store new messages at ids (keep old rows where ~keep_mask)."""
    zb = pcfg.comm.zbuf
    if zb.bits:
        packed, scale = zb.codec().encode(jax.lax.stop_gradient(val))
        old_c, old_s = buf["codes"][ids], buf["scale"][ids]
        m = keep_mask[..., None, None]
        return {
            "codes": buf["codes"].at[ids].set(jnp.where(m, packed, old_c)),
            "scale": buf["scale"].at[ids].set(jnp.where(m, scale, old_s)),
        }
    old = buf[ids]
    m = keep_mask[..., None, None]
    return buf.at[ids].set(jnp.where(m, val.astype(buf.dtype), old))


def buffer_structs(pcfg: PipelineConfig, k: int, n: int, seq: int, d: int):
    """ShapeDtypeStructs for one buffer array (m_out or m_in)."""
    zbits = pcfg.comm.zbuf.bits
    if zbits:
        pw = Q.packed_width(d, zbits)
        return {"codes": jax.ShapeDtypeStruct((k, n, seq, pw), jnp.uint8),
                "scale": jax.ShapeDtypeStruct((k, n, seq, 1), jnp.float32)}
    return jax.ShapeDtypeStruct((k, n, seq, d),
                                jnp.dtype(pcfg.buffer_dtype))


# ---------------------------------------------------------------------------
# stage function: scan over this stage's (padded) layers
# ---------------------------------------------------------------------------

def _apply_layer(cfg: ModelConfig, lp, h, positions, window, extra,
                 block_k: int, expert_map=None, moe_ep=None):
    """One live trunk layer (family dispatch).  h: (mb, S, d)."""
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        return Mo._mamba_layer(cfg, lp, h)
    h, _, _ = Mo._attn_ffn_layer(cfg, lp, h, positions, window,
                                 block_k=block_k, expert_map=expert_map,
                                 moe_ep=moe_ep)
    if fam == "audio":                       # decoder cross-attention
        b, se, d = extra.shape
        hk, hd = cfg.num_kv_heads, cfg.head_dim
        dtype = h.dtype
        xk = (extra @ lp["xattn"]["wk"].astype(dtype)).reshape(
            b, se, hk, hd)
        xv = (extra @ lp["xattn"]["wv"].astype(dtype)).reshape(
            b, se, hk, hd)
        xa, _ = L.attention(
            lp["xattn"], L.rmsnorm(lp["norm_x"], h, cfg.norm_eps),
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            positions=positions, window=L.BIG_WINDOW, cross_kv=(xk, xv),
            block_k=block_k)
        h = h + xa
    return h


def make_stage_fn(cfg: ModelConfig, pcfg: PipelineConfig, lay: StageLayout,
                  layer_dims, shared_dims, exp_axes=None, ep_size: int = 0):
    """stage_fn(stage_params, flags, shared_full, h, positions, extra)."""
    if exp_axes:
        def expert_map(name, leaf, e):
            w = jax.lax.dynamic_index_in_dim(leaf, e, 0, keepdims=False)
            ax = exp_axes[name]
            if ax < 0:
                return w
            return jax.lax.all_gather(w, "data", axis=ax, tiled=True)
    else:
        expert_map = None
    if exp_axes and pcfg.moe_mode == "expert_parallel":
        def ep_weights(name, leaf):
            """FSDP-sharded expert weights -> full weights of MY experts.

            leaf: (E, ..., shard, ...) with dim (exp_axes[name]+1)
            sharded over `data`.  Device g needs experts
            [g·E/D, (g+1)·E/D) whose shards live on every device — each
            device ships its local shard of expert e_j to device j
            (weight all_to_all: 1/D the bytes of a zero3 all_gather)."""
            e = leaf.shape[0]
            ne = max(e // ep_size, 1)
            ax = exp_axes[name]
            idx = (jnp.arange(ep_size)[:, None] * e) // ep_size \
                + jnp.arange(ne)[None, :]
            send = leaf[idx]                    # (D, ne, *wdims_local)
            if ax < 0:                          # weight not sharded
                g = jax.lax.axis_index("data")
                return jax.lax.dynamic_index_in_dim(send, g, 0,
                                                    keepdims=False)
            recv = jax.lax.all_to_all(send, "data", split_axis=0,
                                      concat_axis=0, tiled=False)
            out = jnp.moveaxis(recv, 0, 1 + ax)  # D next to sharded dim
            s = out.shape
            return out.reshape(*s[:1 + ax], s[1 + ax] * s[2 + ax],
                               *s[3 + ax:])
        moe_ep = ("data", ep_size, ep_weights)
    else:
        moe_ep = None

    def body(carry, xs):
        h, positions, extra, shared_full = carry
        lp_sh, window, live, shared = xs
        lp = gather_fsdp(lp_sh, layer_dims)

        def live_fn(hh):
            return _apply_layer(cfg, lp, hh, positions, window, extra,
                                pcfg.block_k, expert_map, moe_ep)

        h = jax.lax.cond(live, live_fn, lambda hh: hh, h)
        if lay.shared_attn:
            def shared_fn(hh):
                out, _, _ = Mo._attn_ffn_layer(
                    cfg, shared_full, hh, positions,
                    cfg.sliding_window or hh.shape[1],
                    block_k=pcfg.block_k)
                return out
            h = jax.lax.cond(shared, shared_fn, lambda hh: hh, h)
        return (h, positions, extra, shared_full), None

    def stage_fn(stage_params, flags, shared_sh, h, positions, extra):
        windows, live, shared = flags
        shared_full = gather_fsdp(shared_sh, shared_dims) \
            if lay.shared_attn else shared_sh

        body_ = jax.checkpoint(body) if pcfg.remat else body

        def run(h):
            (h, _, _, _), _ = jax.lax.scan(
                body_, (h, positions, extra, shared_full),
                (stage_params, windows, live, shared))
            return h

        # nested: one checkpoint around the whole stage per tick (backward
        # re-runs the stage forward, re-gathering ZeRO-3 weights; only the
        # stage input is stored — GPipe's standard memory shape) on top of
        # the per-layer checkpoint.  layer: per-layer only (one less
        # recompute, more residency).
        if pcfg.remat and pcfg.remat_mode == "nested":
            return jax.checkpoint(run)(h)
        return run(h)

    return stage_fn


# ---------------------------------------------------------------------------
# pipeline trunk (runs inside shard_map)
# ---------------------------------------------------------------------------

def make_pipeline_fn(cfg: ModelConfig, pcfg: PipelineConfig,
                     lay: StageLayout, layer_dims, shared_dims,
                     exp_axes=None, ep_size: int = 0):
    K = lay.num_stages
    cc = pcfg.comm.activation
    mode = "warmup" if (pcfg.warmup and cc.mode == "aqsgd") else cc.mode
    has_bufs = cc.mode == "aqsgd"
    transfer = make_transfer(mode, cc.fw_bits, cc.bw_bits, cc.stochastic, K,
                             backend=B.resolve_backend(cc.backend))
    stage_fn = make_stage_fn(cfg, pcfg, lay, layer_dims, shared_dims,
                             exp_axes, ep_size)

    def pipeline_fn(stage_params, flags, shared_sh, h_all, extra_all, ids,
                    m_out, m_in, key):
        # strip the stage dim that shard_map left as size-1
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        flags = jax.tree.map(lambda a: a[0], flags)
        if has_bufs:
            m_out = jax.tree.map(lambda a: a[0], m_out)
            m_in = jax.tree.map(lambda a: a[0], m_in)
        k = jax.lax.axis_index("model")
        key = jax.random.fold_in(key, k)
        M, mb, seq, d = h_all.shape
        T = M + K - 1
        positions = jnp.broadcast_to(
            jnp.arange(seq, dtype=jnp.int32), (mb, seq))

        def _read_slices(mo, mi, j):
            """Pre-read the buffer slices tick ``j + k`` consumes: the
            send-side messages of microbatch clip(j) and the recv-side
            messages of microbatch clip(j+1) (the same clip the tick
            itself applies, so the last pre-read is in range even when
            it goes unused)."""
            jp = jnp.clip(j, 0, M - 1)
            jr = jnp.clip(j + 1, 0, M - 1)
            ids_s = jax.lax.dynamic_index_in_dim(ids, jp, 0,
                                                 keepdims=False)
            ids_r = jax.lax.dynamic_index_in_dim(ids, jr, 0,
                                                 keepdims=False)
            return (buffer_read(pcfg, mo, ids_s),
                    buffer_read(pcfg, mi, ids_r))

        def tick(carry, t):
            # buffered modes carry (mo_s, mi_s) — THIS tick's buffer
            # slices, pre-read at the END of the previous tick (after
            # its writes, so the values are identical to an in-tick
            # read).  The transfer's buffer operands are then ready
            # before the stage compute finishes: the next-tick message
            # decode and the activation ppermute overlap the compute
            # instead of serializing after it.  Bit-exact — a pure
            # scheduling change, gated by the pipeline_worker parity
            # suites.
            if has_bufs:
                state_in, outputs, mo, mi, mo_s, mi_s = carry
            else:
                state_in, outputs, mo, mi = carry
            j = t - k
            valid_p = (j >= 0) & (j < M)
            jp = jnp.clip(j, 0, M - 1)
            inp = jnp.where(
                k == 0,
                jax.lax.dynamic_index_in_dim(
                    h_all, jnp.clip(t, 0, M - 1), 0, keepdims=False),
                state_in)
            extra = None if extra_all is None else \
                jax.lax.dynamic_index_in_dim(extra_all, jp, 0,
                                             keepdims=False)
            out = stage_fn(stage_params, flags, shared_sh, inp, positions,
                           extra)
            prev = jax.lax.dynamic_index_in_dim(outputs, jp, 0,
                                                keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid_p, out, prev), jp, 0)

            ids_s = jax.lax.dynamic_index_in_dim(ids, jp, 0, keepdims=False)
            jr = jnp.clip(j + 1, 0, M - 1)
            valid_r = (j + 1 >= 0) & (j + 1 < M)
            ids_r = jax.lax.dynamic_index_in_dim(ids, jr, 0, keepdims=False)
            if not has_bufs:
                mo_s = mi_s = jnp.zeros_like(out, jnp.float32)
            # a message no stage uses (bubble ticks, and the last stage's
            # wrap-around hop) goes out as zeros, which cuts its backward
            # path: a compressed backward would hand it quantization
            # noise (a zero row decodes to +-scale/levels, not 0), and
            # the stage's Jacobian at bubble inputs amplifies that past
            # f32 range within a dozen layers — NaN parameters
            send = jnp.where(valid_p & (k < K - 1), out, 0)
            recv, nmo, nmi = transfer(send, mo_s, mi_s,
                                      jax.random.fold_in(key, t))
            if has_bufs:
                mo = buffer_write(pcfg, mo, ids_s, nmo,
                                  valid_p & (k < K - 1))
                mi = buffer_write(pcfg, mi, ids_r, nmi,
                                  valid_r & (k > 0))
                mo_sn, mi_sn = _read_slices(mo, mi, j + 1)
                return (recv, outputs, mo, mi, mo_sn, mi_sn), None
            return (recv, outputs, mo, mi), None

        outputs0 = jnp.zeros((M, mb, seq, d), h_all.dtype)
        state0 = jnp.zeros((mb, seq, d), h_all.dtype)
        if has_bufs:
            mo_s0, mi_s0 = _read_slices(m_out, m_in, 0 - k)
            (_, outputs, mo, mi, _, _), _ = jax.lax.scan(
                tick, (state0, outputs0, m_out, m_in, mo_s0, mi_s0),
                jnp.arange(T, dtype=jnp.int32))
        else:
            (_, outputs, mo, mi), _ = jax.lax.scan(
                tick, (state0, outputs0, m_out, m_in),
                jnp.arange(T, dtype=jnp.int32))
        if has_bufs:
            restage = lambda a: a[None]
            return (outputs[None], jax.tree.map(restage, mo),
                    jax.tree.map(restage, mi))
        return outputs[None], m_out, m_in

    return pipeline_fn


# ---------------------------------------------------------------------------
# full train step (pjit embed/head/optimizer around the shard_map trunk)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, pcfg: PipelineConfig, mesh,
                    opt_cfg: adamw.AdamWConfig, *, global_batch: int,
                    seq_len: int, buffer_samples: int):
    """Build the jitted pipeline train step + its sharding specs.

    Returns (train_step, specs) where specs describe the expected state
    pytree shardings (used both to place real arrays and to build
    ShapeDtypeStructs in the dry-run).
    """
    K = mesh.shape["model"]
    daxes = data_axes(mesh)
    D = int(np.prod([mesh.shape[a] for a in daxes]))   # batch replicas
    Df = mesh.shape["data"]                            # FSDP shards
    d_ax = daxes if len(daxes) > 1 else daxes[0]
    M = pcfg.microbatches
    assert global_batch % (D * M) == 0, (global_batch, D, M)
    lay = stage_layout(cfg, K)
    comm = pcfg.comm
    has_bufs = comm.mode == "aqsgd"
    trunk_seq = seq_len        # total trunk sequence (patches + text)

    # static per-leaf FSDP dims (global shapes -> in-scan local dims)
    params_shape = jax.eval_shape(
        lambda: to_pipeline_params(
            cfg, Mo.init_params(cfg, jax.random.PRNGKey(0)), K))
    layer_dims = fsdp_dims_tree(params_shape["stages"], Df, 2, shift=2,
                                stage=True)
    shared_shape = params_shape.get("shared_block", {})
    shared_dims = fsdp_dims_tree(shared_shape, Df, 0, shift=0)
    exp_axes = expert_axes(params_shape["stages"], Df) if cfg.has_moe \
        else None

    pipeline_fn = make_pipeline_fn(cfg, pcfg, lay, layer_dims, shared_dims,
                                   exp_axes, Df)
    flags = layer_flags(cfg, lay, trunk_seq)
    dp_bits = comm.dp.bits
    dp_sharded = bool(dp_bits) and comm.dp_wire_spec.sharded
    if dp_bits:
        glayout = GC.bucket_layout(params_shape, comm.dp_group_d)
        dp_seg = C.ring_segment_rows(glayout.rows, D)
        if dp_sharded:
            dp_update = make_dp_sharded_update(mesh, comm, opt_cfg,
                                               glayout)
        else:
            dp_wire = make_dp_grad_wire(mesh, comm)

    # ---- shard_map specs -------------------------------------------------
    def _stage_pspec(leaf):
        spec = [None] * leaf.ndim
        spec[0] = "model"
        fd = _stage_fsdp_dim(leaf, Df)
        if fd is not None:
            spec[fd] = "data"
        return P(*spec)

    def _plain_pspec(leaf):
        spec = [None] * leaf.ndim
        fd = fsdp_dim(leaf.shape, Df, 0)
        if fd is not None:
            spec[fd] = "data"
        return P(*spec)

    stage_specs = jax.tree.map(_stage_pspec, params_shape["stages"])
    shared_specs = jax.tree.map(_plain_pspec, shared_shape)
    flag_specs = (P("model", None),) * 3
    h_spec = P(None, d_ax, None, None)
    _bp = P("model", d_ax, None, None)
    if not has_bufs:
        buf_spec = P(None)
    elif comm.zbuf.bits:
        buf_spec = {"codes": _bp, "scale": _bp}
    else:
        buf_spec = _bp
    extra_spec = P(None, d_ax, None, None) if cfg.family == "audio" \
        else P(None)
    in_specs = (stage_specs, flag_specs, shared_specs, h_spec, extra_spec,
                P(None, d_ax), buf_spec, buf_spec, P())
    out_specs = (P("model", None, d_ax, None, None), buf_spec, buf_spec)

    smap = shard_map(pipeline_fn, mesh, in_specs, out_specs)

    # ---- loss -------------------------------------------------------------
    def loss_from_hidden(params, h, targets, mask):
        def chunk_loss(args):
            hh, tt, mm = args
            logits = Mo.lm_logits(params, cfg, hh)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, tt[..., None], axis=-1)[..., 0]
            return jnp.sum((lse - gold) * mm), jnp.sum(mm)

        # chunk over the *sequence* dim (batch stays data-sharded so every
        # device participates in every chunk); h: (M, Bmb, S, d)
        seq = h.shape[2]
        n_chunk = 1
        for c in range(min(pcfg.loss_chunks, seq), 0, -1):
            if seq % c == 0:
                n_chunk = c
                break

        def split(x):
            x = x.reshape(*x.shape[:2], n_chunk, seq // n_chunk,
                          *x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        nll, cnt = jax.lax.map(jax.checkpoint(chunk_loss),
                               (split(h), split(targets), split(mask)))
        return jnp.sum(nll) / jnp.maximum(jnp.sum(cnt), 1.0)

    # ---- the step ----------------------------------------------------------
    # batch convention: every batch leaf is microbatch-major,
    # (M, D*mb, ...), so no cross-device resharding is ever needed between
    # the pjit embed/loss sections and the shard_map pipeline.
    def train_step(state, batch, key):
        params = state["params"]

        def loss_fn(params):
            tokens = batch["tokens"]              # (M, Bmb, n_text)
            h = Mo.embed_tokens(params, cfg, tokens, batch.get("patches"))
            h = h.astype(cfg.jax_dtype)
            seq = h.shape[2]
            positions = jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32), h.shape[1:3])
            # the prefix layers and the encoder run here, under GSPMD:
            # their attention kernels run per data shard of the rows
            with L.rows_over(mesh, d_ax):
                for i, lp in enumerate(params.get("prefix", [])):
                    w = cfg.layer_window(i, seq)
                    h = jax.vmap(lambda hh: Mo._attn_ffn_layer(
                        cfg, lp, hh, positions, w,
                        block_k=pcfg.block_k)[0])(h)
                if cfg.family == "audio":
                    enc = jax.vmap(lambda fr: Mo.encode_audio(
                        params, cfg, fr, remat=pcfg.remat,
                        block_k=pcfg.block_k))(batch["frames"])
                    extra_all = enc.astype(cfg.jax_dtype)
                else:
                    extra_all = jnp.zeros((M, 1, 1, 1), cfg.jax_dtype)
            h_all = h
            ids = batch["sample_ids"]             # (M, Bmb)
            shared = params.get("shared_block", {})
            if has_bufs:
                m_out, m_in = state["m_out"], state["m_in"]
            else:
                m_out = m_in = jnp.zeros((1,), cfg.jax_dtype)
            outputs, nmo, nmi = smap(
                params["stages"], flags, shared, h_all, extra_all, ids,
                m_out, m_in, key)
            h_out = outputs[K - 1]                # (M, Bmb, S, d)
            if cfg.num_patches:
                h_out = h_out[:, :, cfg.num_patches:]
            loss = loss_from_hidden(params, h_out, batch["targets"],
                                    batch["mask"])
            return loss, (nmo, nmi)

        (loss, (nmo, nmi)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if dp_sharded:
            # ZeRO-sharded path: compressed reduce-scatter, segment-
            # owner AdamW, and the parameter all-gather all run inside
            # `make_dp_sharded_update`'s shard_map; only the (cheap)
            # flatten/unflatten between leaf layout and bucket layout
            # happens at the pjit level.
            bucket = GC.flatten_bucket(replicate_leaves(mesh, grads),
                                       glayout)
            pb = GC.flatten_bucket(replicate_leaves(mesh, params),
                                   glayout)
            pad = dp_seg * D - glayout.rows
            if pad:
                pb = jnp.pad(pb, ((0, pad), (0, 0)))
            pb = pb.reshape(D, dp_seg, glayout.group_d)
            opt = state["opt"]
            new_pb, new_dp_err, new_mu, new_nu, new_step = dp_update(
                bucket, state["dp_error"], pb, opt["mu"], opt["nu"],
                opt["step"], jax.random.fold_in(key, 977))
            new_params = GC.unflatten_bucket(new_pb, glayout, params)
            new_state = {"params": new_params,
                         "opt": {"mu": new_mu, "nu": new_nu,
                                 "step": new_step},
                         "dp_error": new_dp_err}
        else:
            if dp_bits:
                bucket = GC.flatten_bucket(
                    replicate_leaves(mesh, grads), glayout)
                mean, new_dp_err = dp_wire(bucket, state["dp_error"],
                                           jax.random.fold_in(key, 977))
                grads = GC.unflatten_bucket(mean, glayout, grads)
            new_params, new_opt = adamw.apply_updates(
                opt_cfg, params, grads, state["opt"])
            new_state = {"params": new_params, "opt": new_opt}
            if dp_bits:
                new_state["dp_error"] = new_dp_err
        if has_bufs:
            new_state["m_out"] = nmo
            new_state["m_in"] = nmi
        return new_state, {"loss": loss}

    # ---- state / batch specs (pjit level) ----------------------------------
    pspecs = pipeline_param_specs(mesh, params_shape)
    if dp_sharded:
        # segment-partitioned bucket moments: one owned segment per DP
        # rank, the same placement pattern as dp_error
        seg_sh = NamedSharding(mesh, P(d_ax, None, None))
        opt_specs = {"mu": seg_sh, "nu": seg_sh,
                     "step": NamedSharding(mesh, P())}
    elif opt_cfg.state_bits:
        def qspec(ns):
            scale_spec = P(*ns.spec[:-1], None) if len(ns.spec) else P()
            return {"codes": ns, "scale": NamedSharding(mesh, scale_spec)}
        moment_specs = jax.tree.map(qspec, pspecs,
                                    is_leaf=lambda x: isinstance(
                                        x, NamedSharding))
    else:
        moment_specs = pspecs
    if not dp_sharded:
        opt_specs = {"mu": moment_specs, "nu": moment_specs,
                     "step": NamedSharding(mesh, P())}
    state_specs = {"params": pspecs, "opt": opt_specs}
    if dp_bits:
        state_specs["dp_error"] = NamedSharding(mesh, P(d_ax, None, None))
    if has_bufs:
        bspec = NamedSharding(mesh, P("model", d_ax, None, None))
        if comm.zbuf.bits:
            bspec = {"codes": bspec, "scale": bspec}
        state_specs["m_out"] = bspec
        state_specs["m_in"] = bspec
    batch_specs = {
        "tokens": NamedSharding(mesh, P(None, d_ax, None)),
        "targets": NamedSharding(mesh, P(None, d_ax, None)),
        "mask": NamedSharding(mesh, P(None, d_ax, None)),
        "sample_ids": NamedSharding(mesh, P(None, d_ax)),
    }
    if cfg.family == "vlm":
        batch_specs["patches"] = NamedSharding(
            mesh, P(None, d_ax, None, None))
    if cfg.family == "audio":
        batch_specs["frames"] = NamedSharding(
            mesh, P(None, d_ax, None, None))

    step = jax.jit(train_step,
                   in_shardings=(state_specs, batch_specs, None),
                   out_shardings=(state_specs, None),
                   donate_argnums=(0,))
    meta = {
        "state_specs": state_specs, "batch_specs": batch_specs,
        "layout": lay, "microbatch": global_batch // D // M, "m": M,
        "params_shape": params_shape, "trunk_seq": trunk_seq,
        "buffer_samples": buffer_samples,
    }
    return step, meta


def make_state_structs(cfg: ModelConfig, pcfg: PipelineConfig, meta,
                       mesh, *, global_batch: int, seq_len: int,
                       opt_state_bits: int = 0):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    dt = cfg.jax_dtype
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dt), meta["params_shape"])
    daxes = data_axes(mesh)
    D = int(np.prod([mesh.shape[a] for a in daxes]))
    comm = pcfg.comm
    if comm.dp.bits and comm.dp_wire_spec.sharded:
        # segment-partitioned bucket moments (one segment per DP rank)
        opt = jax.eval_shape(lambda p: init_sharded_opt(pcfg, p, D),
                             meta["params_shape"])
    else:
        if opt_state_bits:
            def qstruct(s):
                return {"codes": jax.ShapeDtypeStruct(s.shape, jnp.uint8),
                        "scale": jax.ShapeDtypeStruct(
                            (*s.shape[:-1], 1), jnp.float32)}
            moments = jax.tree.map(qstruct, params)
        else:
            moments = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                params)
        opt = {"mu": moments, "nu": moments,
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state = {"params": params, "opt": opt}
    if comm.dp.bits:
        # derived by eval_shape of the ONE init function so the struct
        # cannot drift from the layout `make_train_step` traces against
        # (tests/test_grad_compress.py pins this on the worker meshes)
        state["dp_error"] = jax.eval_shape(
            lambda p: init_dp_error(pcfg, p, D), meta["params_shape"])
    if comm.mode == "aqsgd":
        K = mesh.shape["model"]
        daxes = data_axes(mesh)
        D = int(np.prod([mesh.shape[a] for a in daxes]))
        n_loc = meta["buffer_samples"]
        state["m_out"] = buffer_structs(pcfg, K, D * n_loc,
                                        meta["trunk_seq"], cfg.d_model)
        state["m_in"] = buffer_structs(pcfg, K, D * n_loc,
                                       meta["trunk_seq"], cfg.d_model)
    n_text = seq_len - (cfg.num_patches or 0)
    m = meta["m"]
    bmb = global_batch // m
    batch = {
        "tokens": jax.ShapeDtypeStruct((m, bmb, n_text), jnp.int32),
        "targets": jax.ShapeDtypeStruct((m, bmb, n_text), jnp.int32),
        "mask": jax.ShapeDtypeStruct((m, bmb, n_text), jnp.float32),
        "sample_ids": jax.ShapeDtypeStruct((m, bmb), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            (m, bmb, cfg.num_patches, cfg.d_model), dt)
    if cfg.family == "audio":
        batch["frames"] = jax.ShapeDtypeStruct(
            (m, bmb, cfg.encoder_seq, cfg.d_model), dt)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return state, batch, key
