"""Model compute: device time per step of remat's recompute, the
operations under ``rematted_computation`` whatever their scope
(`harness.scopes`), in ms, on the first chip.  A second cut of the
step: it overlaps `attn_ms` and `ffn_ms`, and is no part of the
partition the scope metrics make."""
from harness import scopes


def read(run):
    return scopes.ms_per_step(run,
                              lambda scope, phase: phase == "recompute")
