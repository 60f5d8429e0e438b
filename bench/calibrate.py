#!/usr/bin/env python3
"""Readings that set a training cell's correctness limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--variants program,bf16,half_batch] \
        [--precisions default,highest] [--out FILE]

For each seed, each variant of the program runs the cell's set-up
exactly as a benchmark run does (no window), then the
plain reference runs them once at each matmul precision of
``--precisions`` (default: the configuration's ``matmul_precision``),
and every compared number of `harness.compare` is printed per (seed,
variant, reference precision) as a JSON line:

* ``program`` — the program as the configuration states it: the lower
  readings;
* ``bf16`` — the control: the program's own bfloat16 compute path
  (``ModelConfig.dtype``), the nearest precision below float32;
* ``half_batch`` — a planted fault: half of each batch's rows masked
  out, the mean taken over the rest;
* ``stale_delta`` — a planted fault in the forward codec: the AQ-SGD
  sender (``core/boundary.py::encode_delta``) returns the previous
  message in place of the updated one.

A step that returns its state unchanged needs no run: its change reads
1 on every leaf.  The benchmark's own runs never run this script.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

VARIANTS = {
    "program": {},
    "bf16": {"dtype": "bfloat16"},
    "half_batch": {"feed": "half_batch"},
    "stale_delta": {"patch": "stale_delta"},
}


def half_batch(b: dict) -> dict:
    b = dict(b)
    mask = b["mask"].copy()
    mask[mask.shape[0] // 2:] = 0.0
    b["mask"] = mask
    return b


@contextlib.contextmanager
def stale_delta():
    """The AQ-SGD sender keeps the previous message: its packed codes
    and scale are made, the decoded delta is never added."""
    import jax
    from repro.core import boundary
    orig = boundary.encode_delta

    def enc(a, m, **kw):
        packed, scale, _ = orig(a, m, **kw)
        return packed, scale, m
    boundary.encode_delta = enc
    jax.clear_caches()
    try:
        yield
    finally:
        boundary.encode_delta = orig
        jax.clear_caches()


def readings(cell, seed: int, variants, log, precisions=(None,)) -> list:
    """[(variant, reference precision, compared numbers)] for one
    seed."""
    from harness import sim_train
    progs = {}
    batches = None
    for v in variants:
        opts = VARIANTS[v]
        patch = stale_delta() if opts.get("patch") \
            else contextlib.nullcontext()
        with patch:
            P = sim_train.Program(cell, seed, log, dtype=opts.get("dtype"))
            P.first_epoch(feed=half_batch if opts.get("feed") else None)
        progs[v] = (P.readings, P.aqsgd)
        batches = P.batches
        P.free()
    out = []
    for prec in precisions:
        ref = sim_train.reference(cell, seed, batches, log, prec)
        prec = prec or cell.config["matmul_precision"]
        out += [(v, prec, sim_train.gaps(r, ref, aq))
                for v, (r, aq) in progs.items()]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,bf16,half_batch")
    ap.add_argument("--precisions", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run as bench_run
    from harness import device, spec
    cell = spec.resolve(spec.load_spec(ROOT), args.workload)
    sys.path.insert(0, bench_run.SRC)
    bench_run.setup_jax_cache()
    device.require_chips(cell.chips)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        precs = args.precisions.split(",") if args.precisions else [None]
        for v, prec, vals in readings(cell, seed, args.variants.split(","),
                                      log, precs):
            line = json.dumps({"workload": cell.name, "seed": seed,
                               "variant": v, "reference": prec, **vals})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
