"""The activation-boundary codec's kernels (``kernels/quant_pack.py``
of the program) as the compiled step names them.

The trainer's boundary runs three of them: the AQ-SGD sender
``delta_quantize_pack`` (forward, at ``fw`` bits) and, on the backward
gradient at ``bw`` bits, ``quantize_pack`` and ``unpack_dequant``.
They are memory-bound: a few operations per element against 4 to 12
bytes, so the least time one can take is the bytes it moves in HBM
over the HBM bandwidth.  Those bytes are read from the compiled step
(`harness.hlo.hbm_bytes`), because the compiler decides which operands
stay in HBM and which it keeps in the core's own memory.  At gpt2-xl
width and batch 4 × 1024 on a v5e (compile of the chip's step): the
sender reads the activation (26.2 MB) and writes the codes (3.3 MB),
the scales and the new message (26.2 MB) in HBM, 55.7 MB at two
boundaries and 81.9 MB (the stored message read too) at the third:
193.4 MB and 0.236 ms at 819 GB/s a step.  The noise and both backward
kernels' operands sit in the core's memory.
"""
from __future__ import annotations

CODEC = ("delta_quantize_pack", "quantize_pack", "unpack_dequant")


def kernel_of(op_name: str) -> str:
    """The kernel an HLO instruction name stands for.  The compiler
    names a kernel's custom call after the jitted function around it,
    with the transformations applied to it and a ``.<n>`` suffix
    (``jvp_jit_delta_quantize_pack__.3``,
    ``transpose_jvp_jit_unpack_dequant___.4``); the longest kernel name
    of ``CODEC`` it holds is the kernel, else the name itself."""
    base = op_name.split(".")[0]
    for k in sorted(CODEC, key=len, reverse=True):
        if k in base:
            return k
    return base
