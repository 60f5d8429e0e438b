"""Trainer step: device time per step of the operations under none of
the program's scopes (`harness.scopes`): layout copies and constants
the compiler adds, the per-stage slices of the stacked weights, and
whatever a later change leaves unnamed; in ms, on the first chip.
With the seven scope metrics it partitions the step's summed operation
time.  The largest such operations are written to the log."""
from harness import scopes


def read(run):
    ms = scopes.ms_per_step(run, lambda scope, phase: scope is None)
    if ms is not None:
        run.log("bench: largest unscoped operations, ms a step: "
                + ", ".join(f"{n} {t:.3f}" for n, t in scopes.unscoped(run)))
    return ms
