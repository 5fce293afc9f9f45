"""AdamW with cosine schedule, global-norm clipping, ZeRO-1 sharding.

Built from scratch (no optax).  The optimizer state mirrors the parameter
pytree; ``zero1_logical`` augments each moment's logical spec with the
``data`` axis on its largest shardable dimension, giving ZeRO-1 optimizer-
state sharding under the same rules engine that shards everything else —
XLA then materializes the reduce-scatter/all-gather pair in the update.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    # Not settable: what the update always does, as the benchmark's mixes
    # state it.  The moments are ZeRO-1 sharded over the data axes
    # (``state_logical``); the gradients are reduced in their own dtype.
    zero1: bool = dataclasses.field(default=True, init=False)
    grad_reduce_dtype: Optional[str] = dataclasses.field(default=None,
                                                         init=False)


def schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init_state(params) -> dict:
    zeros = lambda: jax.tree.map(  # noqa: E731
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return {"mu": zeros(), "nu": zeros(),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def apply_updates(cfg: AdamWConfig, params, grads, state):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = schedule(cfg, step)
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)

    b1, b2 = cfg.beta1, cfg.beta2
    t = step.astype(jnp.float32)
    c1 = 1 - b1 ** t
    c2 = 1 - b2 ** t

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32)
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        u = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
        if p.ndim >= 2:   # decoupled weight decay on matrices only
            u = u + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), mu, nu

    out = jax.tree.map(upd, params, grads, state["mu"], state["nu"])
    new_params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_mu = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_nu = jax.tree.map(lambda o: o[2], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    metrics = {"lr": lr, "grad_norm": gnorm}
    return new_params, {"mu": new_mu, "nu": new_nu, "step": step}, metrics


# -- ZeRO-1 logical specs --------------------------------------------------------


def zero1_logical(param_logical, param_shape, mesh, rules):
    """Moment spec = param spec + 'data' sharding on the largest dim that the
    param spec leaves unsharded and that the data axes divide evenly."""
    data_ways = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            data_ways *= mesh.shape[ax]
    used = rules.spec_for(param_logical, param_shape, mesh)
    best, best_size = None, 0
    for i, (name, dim) in enumerate(zip(param_logical, param_shape)):
        already = i < len(used) and used[i] is not None
        if already or name == "layers":
            continue
        if dim % data_ways == 0 and dim > best_size:
            best, best_size = i, dim
    if best is None:
        return tuple(param_logical)
    out = list(param_logical)
    out[best] = "zero1"
    return tuple(out)


def state_logical(params_logical, params_shapes, mesh, rules):
    """Logical specs for the optimizer state pytree (ZeRO-1 moments)."""
    mom = jax.tree.map(
        lambda lg, sh: zero1_logical(lg, sh, mesh, rules),
        params_logical, params_shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    return {"mu": mom, "nu": mom, "step": ()}
