#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic and own files are found by name
(`harness.spec`).  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics and the
device's busy time.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, the
numbers of the correctness comparison each beside its limit.  The same
numbers are the last lines of standard error.

Exits 2 without a result when the checkout holds no program (``src/``)
or the spec is malformed, 3 when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _err(*a):
    print(*a, file=sys.stderr, flush=True)


def setup_jax_cache():
    """The program's compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), with every program
    kept, so that only a checkout's first run compiles."""
    import jax
    from repro import env
    where = env.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import device, spec
    try:
        cell = spec.resolve(spec.load_spec(ROOT), args.workload)
    except (spec.SpecError, KeyError, ValueError) as e:
        _err(f"bench: {e}")
        return 2
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _err(f"bench: no program under {SRC}; run from a checkout of "
             f"the repository")
        return 2
    sys.path.insert(0, SRC)
    setup_jax_cache()
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        _err(f"bench: {e}")
        return 3
    d = devices[0]
    _err(f"bench: {cell.name} seed {args.seed} on {len(devices)} x "
         f"{d.device_kind} ({d.platform})")

    import importlib
    runner = importlib.import_module(f"harness.{cell.traffic['kind']}")
    result = runner.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START,
                        devices=devices, log=_err)
    _err(f"correct {result['correct']}")
    for name, c in result["checks"].items():
        _err(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
