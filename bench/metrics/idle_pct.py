"""Device: 1 - busy / traced window, in %, where busy is the union of
the operation intervals; averaged over the chips the cell uses."""
from harness import metrics


def read(run):
    busy, window = metrics.busy_window(run)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
