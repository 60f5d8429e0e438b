"""Launcher loop: the median device-idle gap between the end of one
step program and the start of the next on the first chip, in ms — the
host's time between steps (the feed, the loss read-back)."""
import statistics

from harness import metrics


def read(run):
    devs = metrics.devices(run)
    if not devs:
        return None
    progs = sorted(metrics.step_programs(run, devs[0]), key=lambda x: x[1])
    gaps = [b[1] - a[2] for a, b in zip(progs, progs[1:])]
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
