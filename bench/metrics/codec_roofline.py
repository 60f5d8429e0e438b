"""Boundary codec: the codec kernels' share of their roofline, in %.
They are memory-bound: the bytes each kernel reads and writes in HBM
(`harness.hlo.hbm_bytes` of its operands and results in the compiled
step; those the compiler keeps in the core's own memory are left out)
over the chip's HBM bandwidth is the least time it can take; summed
over the kernels' executions in the window and divided by their
device time."""
from harness import metrics
from kernels import quant_pack as Q


def read(run):
    devs = metrics.devices(run)
    if not devs:
        return None
    lo, hi = metrics.window(run)
    need = took = 0.0
    for n, s, e in metrics.ops_of_kind(run, devs[0], "kernel"):
        if Q.kernel_of(n) not in Q.CODEC:
            continue
        need += run.ops[n]["hbm_bytes"]
        took += min(e, hi) - max(s, lo)
    if took <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / took
