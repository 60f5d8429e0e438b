#!/usr/bin/env python3
"""Smoke run of the AQ-SGD trainer on a TPU, in one process.

Phases on one chip (the default):

1. device  — refuse anything but a TPU; print its kind, the device count
             and the JAX version.
2. parity  — every `repro.core.boundary` op on the ``pallas`` (Mosaic)
             and ``reference`` backends from the same seeded inputs:
             codes, scales, buffers and decoded values must be
             bit-identical.  Activation ops at gpt2-xl width (d = 1600,
             rows = batch x seq), DP-wire ops at group_d = 512, bits
             2 / 4 / 8, plus a ragged row count.
3. trainer — `repro.launch.runner.run_sim_training` (what
             ``python -m repro.launch.train`` runs) on gpt2-xl-paper at
             its published widths, depth cut to fit one 16 GB chip,
             4 stages (3 compressed boundaries), seq 1024, AQ-SGD
             fw4/bw8 on a sample set half the size of the steps' worth
             of batches (later steps take the delta path), then the same
             steps with ``mode=fp32`` in the same process.  Every loss
             must be finite, and step 0 — a first visit, so the
             AQ-SGD forward pass is the fp32 one — must match fp32 to
             ``STEP0_RTOL``.

With ``--four-chips`` (a 2x2 v5e host) it runs only:

4. pipeline — `repro.launch.train.run_distributed` on a (data=1,
              model=4) mesh, AQ-SGD fw4/bw8 against ``mode=fp32``, and a
              check that every stage's parameters and buffers live on
              four distinct devices.
5. dp wire  — a (data=2, model=2) mesh with the 4-bit ``ring`` DP
              gradient wire against ``psum`` (AQ-SGD from step 0, no
              warm-up epoch): bit-identical losses.

Every phase runs even when an earlier one failed, so one run reports
every fault; the last line of stdout is ``{"ok": true, "device":
{...}}``, printed only when every phase passed, and any failure exits
non-zero.
Times printed on the way come from this one smoke run; they are not a
benchmark.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "gpt2-xl-paper"
TRAIN_LAYERS = 12        # of 48: 449M params, 12.2 GiB per step (remat)
BATCH, SEQ, STEPS = 4, 1024, 6
STAGES = 4
D_MODEL, GROUP_D = 1600, 512
# step 0 of AQ-SGD vs fp32: the same forward values through two
# differently fused programs, whose f32 matmuls run at the TPU's default
# (bf16-pass) precision — fusion-order noise only.  One v5e run of the
# 12-layer trainer measured 6.0e-6.
STEP0_RTOL = 2e-5


def _log(*a):
    print(*a, flush=True)


def _require(ok, message: str):
    """A phase check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(message)


# ---------------------------------------------------------------------------
# phase 2: per-op backend parity
# ---------------------------------------------------------------------------

def _boundary_cases(bits: int, rows: int, d: int, group_d: int, n: int,
                    seed: int):
    """(name, fn(*arrays, backend), arrays) for every boundary op.  The
    receivers get payloads the reference encoders produced, so both
    backends decode the same bytes."""
    import jax
    import jax.numpy as jnp
    from repro.core import boundary as B

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = jax.random.normal(ks[0], (rows, d), jnp.float32)
    m = 0.1 * jax.random.normal(ks[1], (rows, d), jnp.float32)
    g = 1e-3 * jax.random.normal(ks[2], (rows, group_d), jnp.float32)
    s = 1.1 * jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    lv = (1 << bits) - 1
    total = jax.random.randint(ks[3], (rows, group_d), 0, n * lv + 1,
                               jnp.int32)
    acc = jax.random.randint(ks[4], (rows, group_d), 0, 3 * lv + 1,
                             jnp.int32)
    key = ks[5]
    ref = dict(backend="reference")
    pa, sa, _ = B.encode_delta(a, m, bits=bits, stochastic=True, key=key,
                               **ref)
    px, sx = B.encode(a, bits=bits, stochastic=True, key=key, **ref)
    pg = B.encode_with_scale(g, s, bits=bits, stochastic=True, key=key,
                             **ref)
    sums = B.pack_sums(total, bits=bits, n=n, **ref)
    kw = dict(bits=bits)
    return [
        ("encode_delta", lambda a, m, k, be: B.encode_delta(
            a, m, stochastic=True, key=k, backend=be, **kw), (a, m, key)),
        ("encode_delta/round", lambda a, m, be: B.encode_delta(
            a, m, backend=be, **kw), (a, m)),
        ("decode_accumulate", lambda p, s_, m, be: B.decode_accumulate(
            p, s_, m, backend=be, **kw), (pa, sa, m)),
        ("encode", lambda x, k, be: B.encode(
            x, stochastic=True, key=k, backend=be, **kw), (a, key)),
        ("encode/round", lambda x, be: B.encode(x, backend=be, **kw),
         (a,)),
        ("decode", lambda p, s_, be: B.decode(
            p, s_, d=d, backend=be, **kw), (px, sx)),
        ("roundtrip", lambda x, k, be: B.roundtrip(
            x, stochastic=True, key=k, backend=be, **kw), (a, key)),
        ("encode_with_scale", lambda x, s_, k, be: B.encode_with_scale(
            x, s_, stochastic=True, key=k, backend=be, **kw), (g, s, key)),
        ("decode_codes", lambda p, be: B.decode_codes(
            p, d=group_d, backend=be, **kw), (pg,)),
        ("decode_sum_mean", lambda t, s_, be: B.decode_sum_mean(
            t, s_, n=n, backend=be, **kw), (total, s)),
        ("encode_codes_with_scale",
         lambda x, s_, k, be: B.encode_codes_with_scale(
             x, s_, stochastic=True, key=k, backend=be, **kw),
         (g, s, key)),
        ("encode_codes_with_scale/pack",
         lambda x, s_, k, be: B.encode_codes_with_scale(
             x, s_, stochastic=True, key=k, pack=True, backend=be, **kw),
         (g, s, key)),
        ("accumulate_codes", lambda p, c, be: B.accumulate_codes(
            p, c, backend=be, **kw), (pg, acc)),
        ("pack_sums", lambda t, be: B.pack_sums(
            t, n=n, backend=be, **kw), (total,)),
        ("unpack_sums", lambda p, be: B.unpack_sums(
            p, n=n, d=group_d, backend=be, **kw), (sums,)),
    ]


def _bit_mismatch(x, y) -> str:
    """'' when x and y are the same array bit for bit, else a summary."""
    import numpy as np
    x, y = np.asarray(x), np.asarray(y)
    if x.shape != y.shape or x.dtype != y.dtype:
        return f"{x.dtype}{x.shape} vs {y.dtype}{y.shape}"
    u = {1: np.uint8, 2: np.uint16, 4: np.uint32}[x.dtype.itemsize]
    bad = x.view(u) != y.view(u)
    if not bad.any():
        return ""
    diff = np.abs(x.astype(np.float64) - y.astype(np.float64))
    return (f"{int(bad.sum())}/{bad.size} elements differ, "
            f"max |diff| {diff.max()!r}")


def parity_phase(*, rows: int, d: int, group_d: int, bits=(2, 4, 8),
                 ragged_rows: int = 20, n: int = 4, seed: int = 0,
                 log=_log) -> int:
    """Run every boundary op on both backends under jit and require
    bit-identical outputs.  Returns the number of arrays compared."""
    import jax
    from repro.core import boundary as B

    grid = [(b, rows) for b in bits] + [(4, ragged_rows)]
    compared, failures = 0, []
    t0 = time.perf_counter()
    for b, r in grid:
        for name, fn, args in _boundary_cases(b, r, d, group_d, n, seed):
            outs = [jax.tree.leaves(jax.jit(functools.partial(
                fn, be=be))(*args)) for be in B.BACKENDS]
            for i, (x, y) in enumerate(zip(*outs)):
                compared += 1
                why = _bit_mismatch(x, y)
                if why:
                    failures.append(f"{name} bits={b} rows={r} out[{i}]: "
                                    f"{why}")
    log(f"parity: {compared} outputs of {len(B.BACKENDS)} backends "
        f"compared (d={d}, group_d={group_d}, rows {rows} and "
        f"{ragged_rows}, bits {list(bits)}) in "
        f"{time.perf_counter() - t0:.1f}s: "
        f"{'bit-identical' if not failures else 'MISMATCH'}")
    for f in failures:
        log(f"  parity mismatch: {f}")
    _require(not failures, f"{len(failures)} boundary outputs differ "
                           f"between pallas and reference")
    return compared


# ---------------------------------------------------------------------------
# phase 3: the single-host trainer, AQ-SGD against fp32
# ---------------------------------------------------------------------------

def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check_losses(runs: dict, log=_log):
    """Every loss finite; step 0 of the compressed run within STEP0_RTOL
    of the fp32 run."""
    import math
    for label, losses in runs.items():
        _require(losses and all(math.isfinite(x) for x in losses),
                 f"{label}: non-finite loss in {losses}")
    (ca, la), (cb, lb) = list(runs.items())[:2]
    gap = abs(la[0] - lb[0])
    log(f"step 0: {ca} {la[0]!r} vs {cb} {lb[0]!r}, |diff| {gap!r} "
        f"(rel {gap / abs(lb[0])!r}, limit {STEP0_RTOL})")
    _require(gap <= STEP0_RTOL * abs(lb[0]),
             f"step 0 {ca} loss {la[0]!r} != {cb} loss {lb[0]!r}")


def _timed_printer(label, log):
    """print_fn for the trainers: stamps each step line with the wall
    time since the previous one (the first includes compilation)."""
    last = [time.perf_counter()]

    def pr(line):
        now = time.perf_counter()
        log(f"  [{label}] {line}   (+{now - last[0]:.3f}s)")
        last[0] = now
    return pr


def trainer_phase(*, num_layers: int = TRAIN_LAYERS, batch: int = BATCH,
                  seq: int = SEQ, steps: int = STEPS,
                  stages: int = STAGES, smoke: bool = False,
                  seed: int = 0, log=_log) -> dict:
    """AQ-SGD fw4/bw8 then fp32 through `run_sim_training`; returns
    ``{mode: losses}``."""
    import jax
    import numpy as np
    from repro.comm.config import CommConfig
    from repro.configs.base import get_config
    from repro.data.pipeline import Dataset, DatasetConfig
    from repro.launch import runner
    from repro.optim.adamw import AdamWConfig
    from repro.training import simulated as sim

    cfg = get_config(ARCH, smoke=smoke).with_(num_layers=num_layers)
    samples = 2 * batch
    log(f"trainer: {ARCH} d_model={cfg.d_model} heads={cfg.num_heads} "
        f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size}; "
        f"DEPTH CUT to {num_layers} of the published "
        f"{get_config(ARCH).num_layers} layers "
        f"({cfg.params_count() / 1e6:.1f}M params), {stages} stages, "
        f"batch {batch} x seq {seq}, {samples} samples, {steps} steps, "
        f"remat on")
    ds = Dataset(DatasetConfig(num_samples=samples, seq_len=seq,
                               vocab_size=cfg.vocab_size, seed=seed))
    opt = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    runs = {}
    for mode in ("aqsgd", "fp32"):
        tcfg = sim.SimTrainConfig(num_stages=stages, remat=True,
                                  comm=CommConfig(mode=mode),
                                  optimizer=opt)
        state, losses = runner.run_sim_training(
            cfg, tcfg, ds, num_steps=steps, batch_size=batch,
            log_every=1, key=jax.random.PRNGKey(seed),
            print_fn=_timed_printer(mode, log))
        if mode == "aqsgd":
            seen = np.asarray(state["buffers"]["seen"])
            _require(seen.all(), "some samples never reached the delta "
                                 "path")
        del state
        log(f"  [{mode}] peak device bytes in use so far: "
            f"{_peak_bytes()}")
        runs[mode] = losses
    gaps = [a - b for a, b in zip(runs["aqsgd"], runs["fp32"])]
    log(f"trainer: aqsgd - fp32 loss per step: {gaps}")
    _check_losses(runs, log)
    return runs


# ---------------------------------------------------------------------------
# --four-chips: the shard_map pipeline and the DP wire
# ---------------------------------------------------------------------------

def _check_placement(state, n_devices: int, stages: int, log=_log):
    """Every stage-stacked leaf (parameters, AQ-SGD buffers) must have
    its shards on n_devices distinct devices, one stage slice per
    model-axis position — not everything on device 0."""
    import jax
    trees = {"params.stages": state["params"]["stages"]}
    for name in ("m_out", "m_in"):
        if name in state:
            trees[name] = state[name]
    for name, tree in trees.items():
        for leaf in jax.tree.leaves(tree):
            shards = leaf.addressable_shards
            devs = {s.device.id for s in shards}
            slices = {s.index[0].start for s in shards}
            _require(len(devs) == n_devices and len(slices) == stages,
                     f"{name} {leaf.shape}: shards on devices "
                     f"{sorted(devs)}, stage slices {len(slices)}")
        log(f"placement: {name}: {len(jax.tree.leaves(tree))} leaves, "
            f"each on {n_devices} devices in {stages} stage slices")


def _distributed_comparison(title: str, runs: dict, *, layers: int,
                            data: int, model: int, warmup_epochs: int,
                            batch: int, seq: int, steps: int, smoke: bool,
                            seed: int, log) -> dict:
    """`run_distributed` once per ``{label: CommConfig}`` on a
    (data, model) mesh of all four devices; returns ``{label: losses}``
    after the placement and loss checks."""
    import jax
    from repro.configs.base import get_config
    from repro.data.pipeline import Dataset, DatasetConfig
    from repro.launch.train import run_distributed
    from repro.optim.adamw import AdamWConfig

    n = jax.device_count()
    _require(n == data * model, f"{title} needs {data * model} devices, "
                                f"found {n}")
    cfg = get_config(ARCH, smoke=smoke).with_(num_layers=layers)
    samples = 2 * batch
    log(f"{title}: mesh (data={data}, model={model}), {ARCH} "
        f"d_model={cfg.d_model}, {layers} of the published "
        f"{get_config(ARCH).num_layers} layers "
        f"({cfg.params_count() / 1e6:.1f}M params), batch {batch} x "
        f"seq {seq}, {samples} samples, {steps} steps")
    ds = Dataset(DatasetConfig(num_samples=samples, seq_len=seq,
                               vocab_size=cfg.vocab_size, seed=seed))
    opt = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=steps)
    losses = {}
    for label, comm in runs.items():
        state, losses[label] = run_distributed(
            cfg, comm, ds, opt, stages=model, data_par=data,
            microbatches=max(batch // data, 1), batch=batch, seq=seq,
            samples=samples, steps=steps, warmup_epochs=warmup_epochs,
            log_every=1, key=jax.random.PRNGKey(seed),
            print_fn=_timed_printer(f"{title}/{label}", log))
        _check_placement(state, n, model, log)
        del state
        log(f"  [{title}/{label}] peak device bytes in use so far: "
            f"{_peak_bytes()}")
    _check_losses(losses, log)
    return losses


def pipeline_phase(*, layers: int = 48, batch: int = BATCH,
                   seq: int = SEQ, steps: int = STEPS, smoke: bool = False,
                   seed: int = 0, log=_log) -> dict:
    """The shard_map pipeline on (data=1, model=4): AQ-SGD fw4/bw8
    against fp32, first epoch uncompressed as `launch.train` runs it."""
    from repro.comm.config import CommConfig
    return _distributed_comparison(
        "pipeline", {"aqsgd": CommConfig(), "fp32": CommConfig(mode="fp32")},
        layers=layers, data=1, model=4, warmup_epochs=1, batch=batch,
        seq=seq, steps=steps, smoke=smoke, seed=seed, log=log)


def dp_wire_phase(*, layers: int = TRAIN_LAYERS, batch: int = BATCH,
                  seq: int = SEQ, steps: int = STEPS, smoke: bool = False,
                  seed: int = 0, log=_log) -> dict:
    """(data=2, model=2): the 4-bit ``ring`` DP gradient wire against
    ``psum`` must give bit-identical losses.  AQ-SGD runs from step 0:
    the uncompressed warm-up epoch changes nothing this comparison
    reads, and would compile a second step program per wire."""
    from repro.comm.config import CommConfig, PlaneConfig
    losses = _distributed_comparison(
        "dp wire", {w: CommConfig(dp=PlaneConfig(bits=4, wire=w))
                    for w in ("ring", "psum")},
        layers=layers, data=2, model=2, warmup_epochs=0, batch=batch,
        seq=seq, steps=steps, smoke=smoke, seed=seed, log=log)
    _require(losses["ring"] == losses["psum"],
             f"ring and psum losses differ: {losses}")
    log("dp wire: ring and psum losses bit-identical at every step")
    return losses


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pipeline and DP-wire comparisons "
                         "on a four-chip host")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    if SRC not in sys.path:
        sys.path.insert(0, SRC)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default device is {dev.platform!r}, not "
              f"a TPU; refusing to run", file=sys.stderr)
        return 1
    count = len(jax.devices())
    _log(f"device: {dev.device_kind} x {count} ({dev.platform}), "
         f"jax {jax.__version__}")

    from repro import env
    _log(f"compile cache: {env.use_compile_cache()}")
    if args.four_chips:
        phases = [pipeline_phase, dp_wire_phase]
    else:
        phases = [functools.partial(parity_phase, rows=BATCH * SEQ,
                                    d=D_MODEL, group_d=GROUP_D),
                  trainer_phase]
    # every phase runs, so one run reports every fault; any failure
    # still exits non-zero with no ok line
    failed = []
    for phase in phases:
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(getattr(phase, "func", phase).__name__)
        gc.collect()    # a failed phase's arrays sit in traceback cycles
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
