"""AdamW + LR schedules, pure JAX (no optax in this container).

The paper fine-tunes with AdamW, linear warmup then linear decay
(Appendix C); we reproduce exactly that schedule shape.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro import tracing


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 5e-6
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "linear"        # linear | constant
    state_bits: int = 0             # 0 = fp32 moments; 8 = int8-quantized
                                    # moments w/ per-row scales (8-bit Adam
                                    # — in the spirit of the paper, state
                                    # is quantized, not just wires)


def lr_at(cfg: AdamWConfig, step) -> jax.Array:
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "constant":
        return cfg.lr * warm
    decay = jnp.clip(
        (cfg.total_steps - step) /
        jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    return cfg.lr * warm * decay


def _q_enc(x, bits: int):
    """Symmetric per-row quantization of a moment tensor.  Operates on
    the native shape — reshapes across sharded dims would make GSPMD
    replicate the fp32 moments."""
    from repro.core import quantization as Q
    codes, scale = Q.quantize(x, bits, stochastic=False)
    return {"codes": codes, "scale": scale}


def _q_dec(enc, shape, bits: int):
    from repro.core import quantization as Q
    return Q.dequantize(enc["codes"], enc["scale"], bits)


def init_opt_state(params, state_bits: int = 0) -> dict:
    if state_bits:
        enc = lambda p: _q_enc(jnp.zeros_like(p, jnp.float32), state_bits)
        return {"mu": jax.tree.map(enc, params),
                "nu": jax.tree.map(enc, params),
                "step": jnp.zeros((), jnp.int32)}
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def init_bucket_opt_state(n_ranks: int, seg: int, group_d: int) -> dict:
    """ZeRO-sharded moments for the ``ring-sharded`` DP wire: one
    (seg, group_d) segment of the flattened gradient bucket per DP
    rank, stacked (n_ranks, seg, group_d) and sharded one segment per
    segment owner (`training/pipeline.py` places them P(data-axes)).

    Replaces the per-leaf `init_opt_state` tree when the optimizer runs
    in bucket space — each rank only ever reads and writes the moments
    of the segment it owns."""
    zeros = jnp.zeros((n_ranks, seg, group_d), jnp.float32)
    return {"mu": zeros, "nu": jnp.zeros_like(zeros),
            "step": jnp.zeros((), jnp.int32)}


@jax.named_scope(tracing.ADAMW)
def apply_bucket_updates(cfg: AdamWConfig, pbucket, gbucket,
                         state) -> tuple[Any, dict]:
    """AdamW on the flattened (n, seg, group_d) parameter bucket —
    the segment-owner update of the ZeRO-sharded DP wire.

    pbucket: f32 parameter segments (n, seg, group_d), rank i's owned
    segment at index i; gbucket: the segment means
    `ring_ef_reduce_scatter_bucket` left on each owner; state: from
    `init_bucket_opt_state`.  Returns (new pbucket, new state).

    The update math is ELEMENTWISE-IDENTICAL to `apply_updates` on f32
    leaves (same ops, same association), so updating owned segments in
    bucket space and all-gathering the parameter bucket reproduces the
    replicated path bit-for-bit — the loss-parity anchor
    `tests/workers/pipeline_worker.py::check_dp_wire_parity` pins.
    Quantized moments (`state_bits`) are a per-leaf feature and are not
    supported in bucket space."""
    assert not cfg.state_bits, \
        "state_bits (8-bit Adam) is per-leaf; unsupported with the " \
        "bucket-space sharded optimizer (dp_wire='ring-sharded')"
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    g = gbucket.astype(jnp.float32)
    mu = b1 * state["mu"] + (1 - b1) * g
    nu = b2 * state["nu"] + (1 - b2) * jnp.square(g)
    d = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
    d = d + cfg.weight_decay * pbucket.astype(jnp.float32)
    new_p = pbucket.astype(jnp.float32) - lr * d
    return new_p, {"mu": mu, "nu": nu, "step": step}


@jax.named_scope(tracing.ADAMW)
def apply_updates(cfg: AdamWConfig, params, grads, state) -> tuple[Any, dict]:
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    qb = cfg.state_bits

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32)
        if qb:
            mu = _q_dec(mu, p.shape, qb)
            nu = jnp.square(_q_dec(nu, p.shape, qb))  # nu stored as sqrt
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        d = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
        d = d + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * d).astype(p.dtype)
        if qb:
            # sqrt-compand nu: preserves resolution of small 2nd moments
            return new_p, _q_enc(mu, qb), _q_enc(jnp.sqrt(nu), qb)
        return new_p, mu, nu

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_mu = tdef.flatten_up_to(state["mu"])
    flat_nu = tdef.flatten_up_to(state["nu"])
    new = [upd(p, g, m, n)
           for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
    params = tdef.unflatten([t[0] for t in new])
    mu = tdef.unflatten([t[1] for t in new])
    nu = tdef.unflatten([t[2] for t in new])
    return params, {"mu": mu, "nu": nu, "step": step}
