"""Device time of the Mamba-2 layers, read from the program's ``ssm``
and ``ssd`` scopes (``repro.tracing.SSM_SCOPES``; `SCOPES` below is
this benchmark's own copy).

`harness.scopes` reads the scopes that every train step carries; these
two are only in the steps of models with Mamba-2 layers, so they stand
beside them with a table of their own: instruction name -> the
innermost of `SCOPES` on its ``op_name`` path (``ssd`` lies inside
``ssm``), or None.  The times are self times: an operation under
``ssd`` counts for ``ssd`` alone.  The table is built once per traced
run and kept on the run object.  A step that carries neither scope
gives no table, and its readers read nothing.
"""
from __future__ import annotations

import re

from harness import hlo, scopes

SCOPES = ("ssm", "ssd")

_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def classify(op_name: str) -> str | None:
    """The innermost of `SCOPES` on an op_name path, or None."""
    scope = None
    for part in op_name.split("/"):
        if scopes.unwrap(part) in SCOPES:
            scope = scopes.unwrap(part)
    return scope


def table(text: str) -> dict:
    """{instruction name: scope or None} for every instruction of the
    HLO text; empty when no instruction lies under either scope."""
    out = {}
    for comp_name, comp in hlo.parse_hlo(text).items():
        if comp_name == "__entry__":
            continue
        for ins in comp.instrs:
            m = _OP_NAME_RE.search(ins.line)
            out[ins.name] = classify(m.group(1)) if m else None
    return out if any(out.values()) else {}


def scope_ms(run, name: str) -> float | None:
    """Self time per step under the scope ``name``, in ms, on the
    first chip; None when the trace holds no step's operations or the
    step carries neither scope."""
    ops = scopes.step_ops(run)
    if not ops or not run.steps:
        return None
    if getattr(run, "ssm_scope_table", None) is None:
        run.ssm_scope_table = table(run.step_hlo())
    tab = run.ssm_scope_table
    if not tab:
        return None
    return 1e3 * sum(t for n, t in ops if tab.get(n) == name) / run.steps
