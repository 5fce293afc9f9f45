"""RG-LRU recurrent block (RecurrentGemma / Griffin — arXiv:2402.19427).

Real-Gated Linear Recurrent Unit:
    r_t = sigmoid(W_a x_t + b_a)            (recurrence gate)
    i_t = sigmoid(W_x x_t + b_x)            (input gate)
    log a_t = -c * r_t * softplus(Lambda)   (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

The temporal mix is: linear in, causal conv1d (width 4), RG-LRU, gated by a
GeLU branch, linear out.  Training/prefill uses ``jax.lax.associative_scan``
over time (log-depth, parallel) on every platform.  The Pallas kernel
(:mod:`repro.kernels.rglru`) implements the same recurrence with chunked
VMEM tiles but has no caller on the model path.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec

C_GATE = 8.0


def rglru_specs(cfg) -> Dict[str, Any]:
    e = cfg.d_model
    w = cfg.lru_width or e
    return {
        "in_proj": ParamSpec((e, 2 * w), ("embed", "mlp")),      # x, gate
        "conv_w": ParamSpec((cfg.conv_width, w), ((), "mlp")),
        "conv_b": ParamSpec((w,), ("mlp",), "zeros"),
        "w_a": ParamSpec((w, w), ("mlp", "state")),
        "b_a": ParamSpec((w,), ("state",), "zeros"),
        "w_x": ParamSpec((w, w), ("mlp", "state")),
        "b_x": ParamSpec((w,), ("state",), "zeros"),
        "lam": ParamSpec((w,), ("state",), "lru_a"),
        "out_proj": ParamSpec((w, e), ("mlp", "embed")),
    }


def _gates(params, x):
    """log_a: (B,S,W) fp32; gated input (B,S,W) fp32."""
    r = jax.nn.sigmoid((x @ params["w_a"].astype(x.dtype)
                        + params["b_a"].astype(x.dtype)).astype(jnp.float32))
    i = jax.nn.sigmoid((x @ params["w_x"].astype(x.dtype)
                        + params["b_x"].astype(x.dtype)).astype(jnp.float32))
    log_a = -C_GATE * r * jax.nn.softplus(params["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = beta * (i * x.astype(jnp.float32))
    return a, b


def rglru_scan(a, b):
    """h_t = a_t h_{t-1} + b_t via associative scan over axis 1."""
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def _mixer(params, x, cfg, want_cache: bool):
    proj = x @ params["in_proj"].astype(x.dtype)
    w = cfg.lru_width or cfg.d_model
    xb, gate = jnp.split(proj, [w], axis=-1)
    k = params["conv_w"].shape[0]
    pad = jnp.pad(xb, ((0, 0), (k - 1, 0), (0, 0)))
    conv = jnp.zeros_like(xb)
    for i in range(k):
        conv = conv + pad[:, i:i + xb.shape[1]] * \
            params["conv_w"][i].astype(x.dtype)
    conv = conv + params["conv_b"].astype(x.dtype)
    a, b = _gates(params, conv)
    h = rglru_scan(a, b)
    y = h.astype(x.dtype) * jax.nn.gelu(gate, approximate=True)
    out = y @ params["out_proj"].astype(x.dtype)
    if not want_cache:
        return out, None
    cache = {"conv": xb[:, xb.shape[1] - (k - 1):], "h": h[:, -1]}
    return out, cache


def rglru_mixer_apply(params, x, cfg):
    """Temporal mix (training). x: (B,S,E)."""
    return _mixer(params, x, cfg, want_cache=False)[0]


def rglru_prefill(params, x, cfg):
    """Prefill: returns (y, cache) with final recurrent + conv state."""
    return _mixer(params, x, cfg, want_cache=True)


# -- decode -----------------------------------------------------------------------


def rglru_cache_specs(cfg, batch: int) -> Dict[str, Any]:
    w = cfg.lru_width or cfg.d_model
    return {
        "conv": ParamSpec((batch, cfg.conv_width - 1, w),
                          ("batch", (), "mlp"), "zeros"),
        "h": ParamSpec((batch, w), ("batch", "state"), "zeros"),
    }


def rglru_init_cache(cfg, batch: int, dtype):
    w = cfg.lru_width or cfg.d_model
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
            "h": jnp.zeros((batch, w), jnp.float32)}


def rglru_decode(params, x, cfg, cache):
    proj = x @ params["in_proj"].astype(x.dtype)
    w = cfg.lru_width or cfg.d_model
    xb, gate = jnp.split(proj, [w], axis=-1)          # (B,1,W)
    window = jnp.concatenate([cache["conv"], xb], axis=1)
    conv = jnp.einsum("bkw,kw->bw", window, params["conv_w"].astype(x.dtype))
    conv = (conv + params["conv_b"].astype(x.dtype))[:, None, :]
    a, b = _gates(params, conv)                       # (B,1,W)
    h = a[:, 0] * cache["h"] + b[:, 0]
    y = h[:, None, :].astype(x.dtype) * \
        jax.nn.gelu(gate, approximate=True)
    out = y @ params["out_proj"].astype(x.dtype)
    return out, {"conv": window[:, 1:], "h": h}
