"""Reduction of a profiler trace to intervals the metrics read.

The JAX profiler writes an ``.xplane.pb``; `jax.profiler.ProfileData`
reads it.  A TPU shows as planes ``/device:TPU:<n>`` whose ``XLA Ops``
line holds one event per executed HLO operation and whose ``XLA
Modules`` line holds one event per executed program.  An operation's
event is named by its HLO text (``%fusion.12 = f32[...] fusion(...)``);
`op_name` keeps the instruction's name.  A ``while`` loop has an event
of its own that spans the events of its body.  The host's planes hold the harness's own spans
(``bench.*`` annotations).  All times here are in seconds on the
trace's clock.

Everything below works on plain lists of (name, start, end), so the
tests check it on synthetic traces.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    """Per device: op events and program executions; host spans."""
    layout: list = field(default_factory=list)    # (plane, line, events)
    ops: dict = field(default_factory=dict)       # device -> [(n, s, e)]
    modules: dict = field(default_factory=dict)   # device -> [(n, s, e)]
    spans: list = field(default_factory=list)     # [(n, s, e)]

    def window(self, name: str = SPAN_PREFIX + "window"):
        """(start, end) of the host span ``name``."""
        for n, s, e in self.spans:
            if n == name:
                return s, e
        raise KeyError(f"no {name!r} span in the trace")


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device event's name."""
    if " = " in event_name:
        event_name = event_name.split(" = ", 1)[0]
    return event_name.replace("ROOT ", "").strip().lstrip("%")


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(events, lo: float, hi: float) -> float:
    """Seconds within [lo, hi] in which any of ``events`` runs."""
    return sum(e - s for s, e in union(clip([(s, e) for _, s, e in events],
                                            lo, hi)))


def gaps(events, lo: float, hi: float) -> list:
    """Idle (start, end) intervals within [lo, hi] between ``events``."""
    out, t = [], lo
    for s, e in union(clip([(s, e) for _, s, e in events], lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def top_ops(events, lo: float, hi: float, k: int = 10,
            skip=frozenset()) -> list:
    """The ``k`` operation names with the most device time, with it,
    leaving out the names in ``skip`` (loops that span their body's
    operations)."""
    tot = {}
    for n, s, e in events:
        if e > lo and s < hi and n not in skip:
            tot[n] = tot.get(n, 0.0) + min(e, hi) - max(s, lo)
    return sorted(([n, t] for n, t in tot.items()), key=lambda x: -x[1])[:k]


def attribute(gap_list, spans, k: int = 10) -> list:
    """The ``k`` longest idle gaps, each named by the innermost host
    span that covers the gap's midpoint (``host`` when none does)."""
    out = []
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        best, width = "host", None
        for n, ss, se in spans:
            if ss <= mid <= se and (width is None or se - ss < width):
                best, width = n, se - ss
        out.append([best, e - s])
    return sorted(out, key=lambda x: -x[1])[:k]


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    tr = Trace()
    for plane in pd.planes:
        for line in plane.lines:
            tr.layout.append((plane.name, line.name,
                              sum(1 for _ in line.events)))
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(op_name(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                    (tr.ops if line.name == OPS_LINE
                     else tr.modules)[dev] = evs
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns)
                                         * 1e-9))
    return tr
