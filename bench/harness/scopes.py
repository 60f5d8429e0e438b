"""Device time per layer, read from the named scopes the program puts on
its train step.

The program runs each layer of the step under one `jax.named_scope`
(``repro.tracing.SCOPES``; `SCOPES` below is this benchmark's own copy,
as `kernels.quant_pack` keeps its own ``CODEC``).  The compiled step
keeps the scope path of every instruction in its metadata,
``metadata={op_name="jit(train_step)/transpose(jvp(attn))/dot_general"}``,
and the profiler names each device operation by its instruction.  So
one table from instruction name to (scope, phase) puts every device
operation's time down to a layer:

* scope — the innermost component of the path, with transformation
  wrappers such as ``jvp(...)`` and ``transpose(jvp(...))`` unwrapped,
  that is one of `SCOPES`; None when none is;
* phase — ``recompute`` under remat's ``rematted_computation``, else
  ``backward`` under a ``transpose(``, else ``forward``.

A fusion carries the path of its root.  The times are self times:
each operation counts once, under its innermost scope, so the scopes
and the unscoped rest partition the step's summed operation time.
The table is built once per traced run from `Program.step_hlo` and
kept on the run object.  A program whose step carries none of the
scopes gives no table, and every reader of it reads nothing.
"""
from __future__ import annotations

import bisect
import re

from harness import hlo, metrics

SCOPES = ("attn", "ffn", "embed", "lm_head", "boundary", "store", "adamw")
RECOMPUTE = "rematted_computation"

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_WRAPPED_RE = re.compile(r"^[\w\-]+\((.*)\)$")


def unwrap(part: str) -> str:
    """One path component without its transformation wrappers:
    ``transpose(jvp(attn))`` -> ``attn``, ``jvp()`` -> ``''``."""
    m = _WRAPPED_RE.match(part)
    while m:
        part = m.group(1)
        m = _WRAPPED_RE.match(part)
    return part


def classify(op_name: str) -> tuple:
    """(innermost scope of `SCOPES` or None, phase) of an op_name path."""
    scope = None
    for part in op_name.split("/"):
        if unwrap(part) in SCOPES:
            scope = unwrap(part)
    if RECOMPUTE in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    else:
        phase = "forward"
    return scope, phase


def table(text: str) -> dict:
    """{instruction name: (scope, phase)} for every instruction of the
    HLO text that carries an op_name; empty when no instruction's path
    holds any of `SCOPES`."""
    out = {}
    for comp_name, comp in hlo.parse_hlo(text).items():
        if comp_name == "__entry__":
            continue
        for ins in comp.instrs:
            m = _OP_NAME_RE.search(ins.line)
            out[ins.name] = classify(m.group(1) if m else "")
    if not any(scope for scope, _ in out.values()):
        return {}
    return out


def _table(run) -> dict:
    if getattr(run, "scope_table", None) is None:
        run.scope_table = table(run.step_hlo())
    return run.scope_table


def step_ops(run) -> list:
    """(name, seconds) of every non-control operation inside the
    window's step-program executions on the first chip; empty when the
    trace holds none."""
    devs = metrics.devices(run)
    if not devs:
        return []
    progs = sorted((s, e) for _, s, e in metrics.step_programs(run,
                                                                devs[0]))
    starts = [s for s, _ in progs]
    out = []
    for n, s, e in run.trace.ops.get(devs[0], []):
        if run.ops.get(n, {}).get("kind") == "control":
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= progs[i][1]:
            out.append((n, e - s))
    return out


def ms_per_step(run, keep) -> float | None:
    """Summed device time per step, in ms, of the step's operations
    whose (scope, phase) ``keep`` accepts; None when the trace holds no
    step's operations or the program carries no scopes."""
    ops = step_ops(run)
    if not ops or not run.steps:
        return None
    tab = _table(run)
    if not tab:
        return None
    return 1e3 * sum(t for n, t in ops
                     if keep(*tab.get(n, (None, "forward")))) / run.steps


def scope_ms(run, names) -> float | None:
    """Self time per step of the scopes ``names``, in ms."""
    return ms_per_step(run, lambda scope, phase: scope in names)


def unscoped(run, k: int = 8) -> list:
    """The ``k`` unscoped operations with the most time per step, in ms
    (what `unscoped_ms` sums), for the log."""
    tab = _table(run)
    tot = {}
    for n, t in step_ops(run):
        if tab.get(n, (None,))[0] is None:
            tot[n] = tot.get(n, 0.0) + t
    steps = max(run.steps, 1)
    return sorted(([n, 1e3 * t / steps] for n, t in tot.items()),
                  key=lambda x: -x[1])[:k]
