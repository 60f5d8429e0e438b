"""Pieces every family's reference shares: seeded weights, RMSNorm,
cross-entropy, AdamW, and leaf names."""
from __future__ import annotations

import jax
import jax.numpy as jnp

def mm(a, b):
    """a @ b in float32 at the caller's default matmul precision."""
    return jnp.matmul(a, b)


def normal(key, index: int, shape, std: float):
    return jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32) * std


def rmsnorm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def cross_entropy(logits, targets, mask):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lr_at(opt: dict, step):
    """Linear warm-up, then linear decay to ``total_steps``."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    decay = jnp.clip((opt["total_steps"] - step)
                     / max(opt["total_steps"] - opt["warmup_steps"], 1),
                     0.0, 1.0)
    return opt["lr"] * warm * decay


def adamw(opt: dict, params, grads, state):
    """AdamW with bias correction and decoupled weight decay on every
    leaf; ``state`` is (step, mu, nu)."""
    step, mu, nu = state
    step = step + 1
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    lr = lr_at(opt, step)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                              + opt["eps"])
                                  + opt["weight_decay"] * p),
        params, mu, nu)
    return params, (step, mu, nu)


def leaf_names(tree) -> list:
    """'a/b/c' path names of the leaves of ``tree``, in flatten order."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path))
    return out


def layer_norms(tree, stacked: str = "layers"):
    """Per-leaf L2 norms; a leaf under ``stacked`` gives one norm per
    layer (its leading axis), so each layer's slice counts as a leaf."""
    def norm(path, x):
        x = x.astype(jnp.float32)
        top = str(getattr(path[0], "key", ""))
        if top == stacked:
            return jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return jax.tree_util.tree_map_with_path(norm, tree)
