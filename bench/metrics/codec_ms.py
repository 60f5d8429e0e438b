"""Boundary codec: summed device time of the codec's Mosaic kernels
(the program's ``tpu_custom_call`` operations of the activation
boundary) per step, in ms, on the first chip."""
from harness import metrics
from kernels import quant_pack as Q


def read(run):
    devs = metrics.devices(run)
    if not devs or not run.steps:
        return None
    ev = [x for x in metrics.ops_of_kind(run, devs[0], "kernel")
          if Q.kernel_of(x[0]) in Q.CODEC]
    if not ev:
        return None
    lo, hi = metrics.window(run)
    t = sum(min(e, hi) - max(s, lo) for _, s, e in ev)
    return 1e3 * t / run.steps
