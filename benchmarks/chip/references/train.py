"""Reference training: the loss and its gradient row by row, and AdamW
written from Loshchilov & Hutter (decoupled weight decay, on matrices only)
with the schedule and clipping the job's configuration states.

Everything here is float32 at ``highest`` matmul precision unless a lower
parameter precision is asked for, which is how the control is built: the
same reference with its parameters held in bfloat16 between steps.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def lr_at(opt: dict, step: int) -> float:
    """Learning rate of the 1-based optimizer step ``step``: linear warmup
    to ``lr`` over ``warmup_steps``, then a cosine to ``min_lr_ratio * lr``
    at ``total_steps``."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    frac = min(max((step - opt["warmup_steps"]) / span, 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    r = opt["min_lr_ratio"]
    return opt["lr"] * warm * (r + (1.0 - r) * cos)


def leaf_names(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(p.key for p in path) for path, _ in flat]


def leaf_norm(name: str, x) -> jax.Array:
    """Frobenius norm of the units of one reference-layout leaf
    (traceable): a vector over the layers for a ``layers.*`` leaf, a
    scalar for any other."""
    x = x.astype(F32)
    axes = tuple(range(1, x.ndim)) if name.startswith("layers.") else None
    return jnp.sqrt(jnp.sum(x * x, axis=axes))


def norm_arrays(tree) -> Dict[str, jax.Array]:
    """``leaf_norm`` of every leaf of a reference-layout tree (traceable)."""
    return {name: leaf_norm(name, x)
            for name, x in zip(leaf_names(tree), jax.tree.leaves(tree))}


def unit_norms(tree) -> Dict[str, float]:
    """``norm_arrays`` on the host, one entry per unit (``name.layer``)."""
    return expand(jax.jit(norm_arrays)(tree))


def expand(arrs) -> Dict[str, float]:
    """Host copy of ``norm_arrays``' result, one entry per unit."""
    out = {}
    for name, v in arrs.items():
        v = np.asarray(v)
        if v.ndim:
            for i, x in enumerate(v):
                out[f"{name}.{i}"] = float(x)
        else:
            out[name] = float(v)
    return out


def zeros(tree):
    """A float32 tree of zeros shaped like ``tree``."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, F32), tree)


def make_grad_fn(model, cfg: dict):
    """Mean token NLL over the batch and its gradient, summed row by row so
    that one row's activations are live at a time:
    ``fn(params, tokens, labels, zeros(params))``.  The sum is kept in the
    zero tree, which is donated and becomes the gradient, so that the call
    holds no copy of the parameters beyond the gradient and one row's."""

    def row_grad(params, tokens, labels):
        return jax.value_and_grad(model.token_nll_sum)(
            params, tokens[None], labels[None], cfg)

    @functools.partial(jax.jit, donate_argnums=3)
    def fn(params, tokens, labels, zero):
        with jax.default_matmul_precision("highest"):
            def body(carry, row):
                tot, g = carry
                v, gr = row_grad(params, row[0], row[1])
                return (tot + v, jax.tree.map(jnp.add, g, gr)), None

            (tot, g), _ = jax.lax.scan(body, (jnp.zeros((), F32), zero),
                                       (tokens, labels))
            n = tokens.size
            return tot / n, jax.tree.map(lambda x: x / n, g)
    return fn


def decays(name: str, shape) -> bool:
    """Decoupled weight decay on matrices only: a leaf with two or more
    axes per layer (the embedding, projections, convolution weights), not
    the norm weights, biases or per-head vectors such as Mamba-2's
    ``A_log``, ``D`` and ``dt_bias``."""
    return len(shape) - name.startswith("layers.") >= 2


def make_update(opt: dict, decay: List[bool], param_dtype=F32):
    """One AdamW step: ``update(params, grads, mu, nu, lr, t)`` returns the
    new parameters (in ``param_dtype``), the gradients clipped to the global
    norm ``clip_norm``, and the new moments, in the order of the
    arguments.  The four trees are donated, each to its successor, so that
    a step holds no copy of the parameters beyond them."""
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], \
        opt["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(params, grads, mu, nu, lr, t):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        pl, treedef = jax.tree.flatten(params)
        gl, ml, nl = (jax.tree.leaves(x) for x in (grads, mu, nu))
        new_p, new_m, new_n = [], [], []
        for p, g, m, v, dec in zip(pl, gl, ml, nl, decay):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            p32 = p.astype(F32)
            if dec:
                u = u + wd * p32
            new_p.append((p32 - lr * u).astype(param_dtype))
            new_m.append(m)
            new_n.append(v)
        un = lambda xs: jax.tree.unflatten(treedef, xs)  # noqa: E731
        return un(new_p), grads, un(new_m), un(new_n)
    return update


def train(model, cfg: dict, opt: dict, params, batches: List[dict], *,
          param_dtype=F32, rows: Optional[Callable[[int], slice]] = None
          ) -> dict:
    """Run ``len(batches)`` AdamW steps from ``params`` (reference layout,
    float32) and return what the comparison reads: each step's loss, the
    per-unit norms of the first step's clipped gradient and raw gradient,
    and of the change of the parameters over all the steps.

    ``params`` is consumed: its buffers go to the first update.  The
    starting point is kept on the host, so that the device holds the
    parameters, their gradient and the two moments (and, while a gradient
    is summed, one row's gradient and activations), nothing more.

    ``param_dtype`` holds the parameters in that type between steps (the
    control).  ``rows(k)`` restricts step ``k`` to those rows of its batch
    (a planted fault); by default every row counts.
    """
    grad_fn = make_grad_fn(model, cfg)
    names = leaf_names(params)
    update = make_update(opt, [decays(n, p.shape) for n, p in
                               zip(names, jax.tree.leaves(params))],
                         param_dtype)
    # a host copy of a device copy: where the device is the host's own
    # memory, a host copy of ``params`` would hold its buffers, and the
    # update could not take them
    start = [jax.device_get(p.copy()) for p in jax.tree.leaves(params)]
    held = jax.tree.map(lambda p: p.astype(param_dtype), params)
    for p, h in zip(jax.tree.leaves(params), jax.tree.leaves(held)):
        if h is not p:      # the control's copy: the float32 start goes
            p.delete()
    params = held
    mu, nu = zeros(params), zeros(params)
    losses, first_clipped, first_raw = [], None, None
    for k, batch in enumerate(batches):
        sl = rows(k) if rows is not None else slice(None)
        p32 = jax.tree.map(lambda p: p.astype(F32), params)
        loss, grads = grad_fn(p32, batch["tokens"][sl], batch["labels"][sl],
                              zeros(p32))
        del p32
        losses.append(float(loss))
        if k == 0:
            first_raw = unit_norms(grads)
        t = k + 1
        params, clipped, mu, nu = update(params, grads, mu, nu,
                                         jnp.float32(lr_at(opt, t)),
                                         jnp.float32(t))
        if k == 0:
            first_clipped = unit_norms(clipped)
        del grads, clipped
    del mu, nu
    norm = jax.jit(leaf_norm, static_argnums=0)
    delta = {name: norm(name, p.astype(F32) - s)
             for name, p, s in zip(names, jax.tree.leaves(params), start)}
    return {"losses": losses, "grad": first_clipped, "grad_raw": first_raw,
            "delta": expand(delta)}
