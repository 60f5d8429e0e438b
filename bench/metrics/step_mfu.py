"""Trainer step: model FLOPs of a step (`flops.<family>`, recompute not
counted) over the device time of the step program times the chip's
bf16 peak, in %: the whole step's share of the chip's peak while it
runs, which bounds what any one kernel's speed-up can give."""
from harness import metrics, trace as tr


def read(run):
    devs = metrics.devices(run)
    if not devs:
        return None
    ops = run.trace.ops[devs[0]]
    progs = metrics.step_programs(run, devs[0])
    t = sum(tr.busy(ops, s, e) for _, s, e in progs)
    if not progs or t <= 0:
        return None
    flops = run.flops_per_token * run.tokens_per_step * len(progs)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
