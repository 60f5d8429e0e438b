"""Model compute: the matmuls' share of their roofline, in %: the dot
and convolution FLOPs of every matmul operation the window ran (from
the compiled step's HLO, `harness.hlo.op_table`; recompute included),
over the chip's bf16 peak, divided by those operations' device time.
At these sizes the matmuls are bound by FLOPs, so FLOPs alone give
the least time."""
from harness import metrics


def read(run):
    devs = metrics.devices(run)
    if not devs:
        return None
    lo, hi = metrics.window(run)
    flops = took = 0.0
    for n, s, e in metrics.ops_of_kind(run, devs[0], "matmul"):
        if s < lo or e > hi:
            continue
        flops += run.ops[n]["flops"]
        took += e - s
    if took <= 0:
        return None
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
