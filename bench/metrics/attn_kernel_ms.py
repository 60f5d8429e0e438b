"""Model compute: summed device time of the attention core's three
Mosaic kernels (``flash_fwd``, ``flash_bwd_dkv``, ``flash_bwd_dq``) per
step, in ms, on the first chip; nothing where the step runs none."""
from harness import metrics
from kernels import flash_attention as F


def read(run):
    devs = metrics.devices(run)
    if not devs or not run.steps:
        return None
    ev = [x for x in metrics.ops_of_kind(run, devs[0], "kernel")
          if F.kernel_of(x[0]) is not None]
    if not ev:
        return None
    lo, hi = metrics.window(run)
    t = sum(min(e, hi) - max(s, lo) for _, s, e in ev)
    return 1e3 * t / run.steps
