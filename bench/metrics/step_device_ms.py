"""Trainer step: the union of device-operation intervals inside one
execution of the step program, averaged over the window's steps on the
first chip, in ms."""
from harness import metrics, trace as tr


def read(run):
    devs = metrics.devices(run)
    if not devs:
        return None
    progs = metrics.step_programs(run, devs[0])
    if not progs:
        return None
    ops = run.trace.ops[devs[0]]
    return 1e3 * sum(tr.busy(ops, s, e) for _, s, e in progs) / len(progs)
