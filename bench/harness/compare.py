"""The numbers that decide ``correct`` for a training cell.

Each is a gap between what the program produced and what the plain
reference computes from the same seed, read the same way on both
sides (see `readings` of the cell runners):

* ``loss_gap`` — the worst relative gap of the loss over the first
  three steps;
* ``grad_gap`` — the first gradient as the optimizer gets it: per leaf
  (each layer's slice of a stacked array is a leaf) the gap between
  the two L2 norms, over the reference's norm of that leaf or of the
  median leaf, whichever is larger; the worst leaf;
* ``change_gap`` — the same measure on the parameters' change after
  three steps, leaving out the leaves whose reference gradient is
  under a thousandth of the median leaf's (they move by round-off);
* ``boundary_gap`` — (AQ-SGD cells) the stored messages the first step
  wrote, which on a first visit are the activations at the stage
  boundaries: the worst relative L2 distance to the reference's
  activations over the boundaries;
* ``bf16_share_gap`` — (AQ-SGD cells) the share of the first step's
  stored messages that bfloat16 holds exactly, less the same share of
  the reference's activations: about 2^-16 for float32 values and 1
  for a program that holds its activations in bfloat16.  The rounding
  of the matmuls' inputs, which is most of ``boundary_gap``, leaves the
  storage precision the configuration states unseen; this sees it;
* ``revisit_gap`` — (AQ-SGD cells) the same on the first step that
  revisits samples (the first of the second epoch), whose stored
  messages are the previous ones plus the decoded low-bit delta: the
  forward codec on the path the window takes.  Its stochastic 4-bit
  rounding noise dominates it, about a fifth of the delta;
* ``delta_gap`` — (AQ-SGD cells) on that same step, how far the
  program's stored update of each message (new minus previous) falls
  short of taking the reference's change of the activation between the
  sample's two visits (`delta_gap`): the delta codec's own job, with
  its unbiased noise averaged out.

A number passes when it is at most its limit; a missing or non-finite
number fails.
"""
from __future__ import annotations

import math

import numpy as np

ROUNDOFF = 1e-3


def _flat(norms: dict) -> tuple:
    keys = sorted(norms)
    return keys, np.concatenate([np.atleast_1d(np.asarray(norms[k],
                                                          np.float64))
                                 for k in keys])


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖) over leaves."""
    kp, p = _flat(prog)
    kr, r = _flat(ref)
    if kp != kr or p.shape != r.shape:
        raise ValueError("program and reference leaves differ")
    if keep is not None:
        p, r = p[keep], r[keep]
    den = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / den))


def moving_leaves(ref_grad: dict) -> np.ndarray:
    """Mask of leaves whose reference gradient norm is at least
    ``ROUNDOFF`` of the median leaf's."""
    _, g = _flat(ref_grad)
    return g >= ROUNDOFF * np.median(g)


def message_gap(prog, ref) -> float:
    """Worst relative L2 distance over the boundaries between the
    program's stored messages (boundary-major) and the reference's
    activations."""
    worst = 0.0
    for j, a in enumerate(ref):
        a = np.asarray(a, np.float64)
        d = np.asarray(prog[j], np.float64) - a
        worst = max(worst, float(np.linalg.norm(d) / np.linalg.norm(a)))
    return worst


def bf16_share(arrays) -> float:
    """Share of the elements of ``arrays`` that are float32 values
    bfloat16 holds exactly (the low 16 bits of the pattern are zero)."""
    hits = total = 0
    for a in arrays:
        bits = np.ascontiguousarray(a, np.float32).view(np.uint32)
        hits += int(np.count_nonzero((bits & 0xFFFF) == 0))
        total += bits.size
    return hits / total


def bf16_share_gap(prog, ref) -> float:
    return abs(bf16_share(prog) - bf16_share(ref))


def delta_gap(prog_new, prog_prev, ref_new, ref_prev) -> float:
    """Worst |1 - β| over the boundaries, where β is the share of the
    reference's change of a sample's activation between two visits
    that the program's stored message took in the same step:
    β = <m_new - m_prev, a_new - a_prev> / ‖a_new - a_prev‖².
    Stochastic rounding is unbiased, so its noise averages out of β
    over the millions of elements, while a message that does not take
    the delta reads 1."""
    worst = 0.0
    for j, (an, ap) in enumerate(zip(ref_new, ref_prev)):
        d = np.asarray(an, np.float64) - np.asarray(ap, np.float64)
        u = (np.asarray(prog_new[j], np.float64)
             - np.asarray(prog_prev[j], np.float64))
        worst = max(worst, abs(1.0 - float(np.vdot(u, d) / np.vdot(d, d))))
    return worst


def loss_gap(prog: list, ref: list) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for every limited number."""
    checks, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok = ok and good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
