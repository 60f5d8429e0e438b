"""Boundary codec: the bytes the stage boundaries would ship per
trained token, forward messages plus backward gradients over every
boundary, from the program's own per-plane byte models
(``CompressionConfig.fw_wire_bytes`` / ``bw_wire_bytes``, which the
program's collective auditor holds equal to compiled bytes) at the
cell's shapes.  A count, not a time: it catches a change that buys chip
speed by shipping more over the slow link."""


def read(run):
    cc = run.tcfg.comm.activation
    shape = (run.batch, run.seq, run.cell.config["d_model"])
    per_step = (run.cell.traffic["stages"] - 1) * (
        cc.fw_wire_bytes(shape) + cc.bw_wire_bytes(shape))
    return per_step / run.tokens_per_step
