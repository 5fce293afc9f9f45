"""Mixture-of-Experts FFN: the share of a layer's routed experts that this
chip holds, dropless, plus the shared experts.

The router keeps all of the layer's ``num_experts`` outputs: float32
logits, softmax, greedy top-k, the weights renormalised only where the
configuration says so (``norm_topk_prob``), and a balance loss over every
expert's scores (``router_aux``: Switch's over the batch, or DeepSeek-V2's
per sequence).  The layer holds ``held_experts`` of them, experts 0 to
``held - 1``, stacked ``(held, D, F)``.

Every (token, choice) pair that names a held expert is computed; none is
dropped.  The pairs are sorted by expert and each held expert's weights
are applied to its rows by grouped matmuls (``jax.lax.ragged_dot``, XLA's
ragged-dot kernel on TPU), so the work done is the routed rows plus tile
padding.  A pair that names an expert held elsewhere adds nothing here:
in a deployment the chip that holds it computes it, after an all-to-all
that this layer does not have yet.  With every expert held (the default)
the layer is the whole routed FFN.

Layouts: DeepSeekMoE (64 routed top-6 + 2 shared, first layer dense),
DeepSeek-V2-Lite (the same with the published gate), Phi-3.5-MoE (16
routed top-2).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, act_fn, mlp_apply, mlp_specs

F32 = jnp.float32


def moe_specs(cfg) -> Dict[str, Any]:
    e, f, held = cfg.d_model, cfg.expert_d_ff or cfg.d_ff, cfg.held_experts
    specs = {
        "router": ParamSpec((e, cfg.num_experts), ("embed", "experts")),
        "w_gate": ParamSpec((held, e, f), ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((held, e, f), ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((held, f, e), ("experts", "expert_mlp", "embed")),
    }
    if cfg.num_shared_experts:
        specs["shared"] = mlp_specs(
            cfg, d_ff=cfg.num_shared_experts * (cfg.expert_d_ff or cfg.d_ff))
    return specs


def balance_loss(probs, experts, cfg):
    """probs: (B, S, E) router scores; experts: (B, S, k) chosen ids."""
    b, s, ne = probs.shape
    counts = (experts[..., None] == jnp.arange(ne)).sum(axis=(1, 2))  # (B,E)
    if cfg.router_aux == "seq":
        # HF DeepSeek-V2 MoEGate (seq_aux): per sequence, each expert's
        # share of the choices over its uniform share, times its mean score
        ce = counts.astype(F32) / (s * cfg.top_k / ne)
        return cfg.router_aux_coef * jnp.mean(
            jnp.sum(ce * probs.mean(axis=1), axis=-1))
    # Switch: E * sum_e (fraction of tokens routed to e) * (mean score of e)
    f = counts.sum(axis=0).astype(F32) / (b * s)
    return cfg.router_aux_coef * ne * jnp.sum(f * probs.mean(axis=(0, 1)))


def route(params, x, cfg):
    """x: (B, S, D) -> top-k weights (B, S, k) in float32, expert ids
    (B, S, k) over all ``num_experts``, and the balance loss."""
    logits = jnp.einsum("bsd,de->bse", x.astype(F32),
                        params["router"].astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights, experts, balance_loss(probs, experts, cfg)


def zero_stats(cfg) -> Dict[str, jax.Array]:
    """The per-step readings the layers add to: the balance loss and, for
    a model with experts, the counters ``moe_held_rows`` (rows the held
    experts computed, summed over layers) and ``moe_load_max`` (the largest
    held expert's rows over the held experts' mean, the largest over
    layers)."""
    out = {"aux": jnp.zeros((), F32)}
    if cfg.num_experts:
        out.update(moe_held_rows=jnp.zeros((), jnp.int32),
                   moe_load_max=jnp.zeros((), F32))
    return out


def add_stats(total: Dict[str, jax.Array], layer: Dict[str, jax.Array]):
    return {"aux": total["aux"] + layer["aux"],
            "moe_held_rows": total["moe_held_rows"] + layer["moe_held_rows"],
            "moe_load_max": jnp.maximum(total["moe_load_max"],
                                        layer["moe_load_max"])}


def moe_apply(params, x, cfg):
    """x: (B, S, D) -> (y, stats), stats as ``zero_stats`` names them for
    this layer.  The layer holds experts 0 .. ``held - 1``, ``held`` the
    leading axis of its expert weights."""
    b, s, d = x.shape
    k, held, dt = cfg.top_k, params["w_gate"].shape[0], x.dtype
    with jax.named_scope("moe.route"):
        weights, experts, aux = route(params, x, cfg)
        local = experts.reshape(-1)                            # (T*k,)
        mine = local < held
        group = jnp.where(mine, local, held)    # pairs held elsewhere last
        order = jnp.argsort(group, stable=True)
        sizes = (group[:, None] == jnp.arange(held)).sum(
            axis=0).astype(jnp.int32)
        n = sizes.sum()
        # the grouped matmuls leave rows past the held groups undefined,
        # forward and backward: zero them, so that nothing flows from them
        valid = (jnp.arange(order.shape[0]) < n)[:, None]
        rows = jnp.where(valid, x.reshape(-1, d)[order // k], 0)
    with jax.named_scope("moe.experts"):
        act = act_fn(cfg.act)
        h = act(jax.lax.ragged_dot(rows, params["w_gate"].astype(dt),
                                   sizes)) * \
            jax.lax.ragged_dot(rows, params["w_up"].astype(dt), sizes)
        h = jnp.where(valid, h, 0)
        out = jnp.where(valid, jax.lax.ragged_dot(
            h, params["w_down"].astype(dt), sizes), 0)
        # back to (choice, token) order
        back = jnp.argsort(order).reshape(b * s, k).T
        w = jnp.where(mine, weights.reshape(-1), 0.0).reshape(b * s, k).T
        y = jnp.einsum("ktd,kt->td", out[back], w.astype(dt))
        y = y.reshape(b, s, d)
    if cfg.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg)
    load = jnp.where(n > 0, sizes.max() * held / jnp.maximum(n, 1), 0.0)
    return y, {"aux": aux, "moe_held_rows": n,
               "moe_load_max": load.astype(F32)}
