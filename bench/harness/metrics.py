"""Per-layer metrics: one reader per metric in ``metrics/<name>.py``,
found by name, each with ``read(run) -> float | None``.  A reader that
finds nothing to read returns None and the metric is left out of the
result line; it never returns 0 for a share.

The helpers below are what readers share: the traced window, the step
programs' executions on the device, and the operations of a kind.
"""
from __future__ import annotations

import importlib.util

from harness import spec, trace as tr

STEP_PROGRAM = "train_step"


def _load(name: str):
    path = spec.metric_path(name)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_all(entries, run) -> dict:
    return {m["name"]: _load(m["name"]).read(run) for m in entries}


def window(run):
    return run.trace.window()


def devices(run) -> list:
    return sorted(run.trace.ops)


def step_programs(run, dev: int) -> list:
    """Executions of the step program on ``dev`` inside the window."""
    lo, hi = window(run)
    return [(n, s, e) for n, s, e in run.trace.modules.get(dev, [])
            if STEP_PROGRAM in n and s >= lo and e <= hi]


def ops_of_kind(run, dev: int, kind: str) -> list:
    lo, hi = window(run)
    return [(n, s, e) for n, s, e in run.trace.ops.get(dev, [])
            if run.ops.get(n, {}).get("kind") == kind
            and e > lo and s < hi]


def busy_window(run):
    """(device-busy seconds averaged over the chips, window seconds)."""
    lo, hi = window(run)
    devs = devices(run)
    if not devs:
        return 0.0, hi - lo
    b = sum(tr.busy(run.trace.ops[d], lo, hi) for d in devs) / len(devs)
    return b, hi - lo


def breakdown(run) -> dict:
    lo, hi = window(run)
    devs = devices(run)
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    ops = run.trace.ops[devs[0]]
    loops = {n for n, row in run.ops.items() if row["kind"] == "control"}
    return {"device_ops": tr.top_ops(ops, lo, hi, skip=loops),
            "idle_gaps": tr.attribute(tr.gaps(ops, lo, hi),
                                      run.trace.spans)}
