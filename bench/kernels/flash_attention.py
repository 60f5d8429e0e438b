"""The attention core's kernels (``kernels/flash_attention.py`` of the
program) as the compiled step names them.

On a TPU the program runs attention's core as three Mosaic kernels,
named by their ``pallas_call``: ``flash_fwd`` (the online-softmax
forward, in the forward pass and again in remat's recompute),
``flash_bwd_dkv`` and ``flash_bwd_dq`` (the backward).  The compiled
step names each custom call after its kernel with a ``.<n>`` suffix
(``flash_fwd.3``); a program that runs the core as a `lax.scan` has
none of them.
"""
from __future__ import annotations

FLASH = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def kernel_of(op_name: str):
    """The flash kernel an HLO instruction name stands for, else None."""
    base = op_name.split(".")[0]
    return next((k for k in FLASH if k in base), None)
