"""Launcher loop: the 90th percentile of the window's step times on the
host clock (feed, step call and loss read-back), over all steps of the
traced run, in ms."""
import statistics


def read(run):
    if len(run.step_times) < 2:
        return None
    return 1e3 * statistics.quantiles(run.step_times, n=10,
                                      method="inclusive")[-1]
