"""Mamba-2 SSD (state-space duality) block — arXiv:2405.21060.

The model runs the *chunked SSD algorithm* (the paper's Listing 1) in XLA on
every platform: intra-chunk quadratic attention-like term + inter-chunk
recurrent state carry, scanned over chunks.  The Pallas kernel
(:mod:`repro.kernels.ssd`) uses the same blocking but has no caller on the
model path.  ``n_groups = 1`` (B/C shared across heads), D skip
connection, gated RMSNorm, causal conv1d — matching the mamba2-130m
reference.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rms_norm


def ssd_specs(cfg) -> Dict[str, Any]:
    e, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n
    return {
        "in_proj": ParamSpec((e, 2 * di + 2 * n + h), ("embed", "mlp")),
        "conv_w": ParamSpec((cfg.conv_width, conv_dim), ((), "mlp")),
        "conv_b": ParamSpec((conv_dim,), ("mlp",), "zeros"),
        "A_log": ParamSpec((h,), ("heads",), "ones"),
        "D": ParamSpec((h,), ("heads",), "ones"),
        "dt_bias": ParamSpec((h,), ("heads",), "zeros"),
        "norm": ParamSpec((di,), ("mlp",), "zeros"),
        "out_proj": ParamSpec((di, e), ("mlp", "embed")),
    }


def _split_proj(cfg, zxbcdt):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z, xc, b, c, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    return z, xc, b, c, dt


def causal_conv1d(x, w, b):
    """x: (B, S, C); w: (K, C) depthwise; left-padded causal."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):            # K is 4: unrolled taps
        out = out + pad[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
    return jax.nn.silu(out + b.astype(x.dtype))


def ssd_chunked(x, dt, a_log, b, c, chunk: int):
    """Chunked SSD. x: (B,S,H,P); dt: (B,S,H); b,c: (B,S,N) (n_groups=1).

    Returns y: (B,S,H,P).  Exact (fp32 state); ``tests/test_kernels.py``
    checks it against the Pallas kernel, which is checked against the
    step-recurrence oracle in kernels/ssd/ref.py.
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    nc = s // q
    a = -jnp.exp(a_log.astype(jnp.float32))                 # (H,)
    dt = dt.astype(jnp.float32)
    da = dt * a[None, None, :]                              # (B,S,H)
    x_dt = x * dt[..., None].astype(x.dtype)

    def per_chunk(carry, inp):
        h_state = carry                                     # (B,H,P,N) fp32
        xc, dac, bc, cc = inp
        seg = jnp.cumsum(dac, axis=1)                       # (B,q,H)
        total = seg[:, -1]                                  # (B,H)
        # intra-chunk (quadratic) term; mask inside the exp so masked
        # positions neither overflow forward nor produce inf*0 cotangents
        li = seg[:, :, None, :] - seg[:, None, :, :]        # (B,q,q,H)
        mask = jnp.tril(jnp.ones((q, q), bool))
        li = jnp.where(mask[None, :, :, None], li, -jnp.inf)
        decay = jnp.exp(li)
        cb = jnp.einsum("bqn,bsn->bqs", cc.astype(jnp.float32),
                        bc.astype(jnp.float32))
        att = cb[..., None] * decay                         # (B,q,q,H)
        y_intra = jnp.einsum("bqsh,bshp->bqhp", att,
                             xc.astype(jnp.float32))
        # inter-chunk: contribution of the carried state
        state_decay = jnp.exp(seg)                          # (B,q,H)
        y_inter = jnp.einsum("bqn,bhpn->bqhp", cc.astype(jnp.float32),
                             h_state) * state_decay[..., None]
        # state update
        rem = jnp.exp(total[:, None, :] - seg)              # (B,q,H)
        bx = jnp.einsum("bqn,bqhp->bhpn",
                        bc.astype(jnp.float32),
                        xc.astype(jnp.float32) * rem[..., None])
        h_new = h_state * jnp.exp(total)[:, :, None, None] + bx
        return h_new, (y_intra + y_inter).astype(x.dtype)

    # Python-unrolled over chunks (not lax.scan): every chunk's FLOPs appear
    # explicitly in the HLO so cost_analysis reflects the true SSD cost.
    h_state = jnp.zeros((bsz, h, p, n), jnp.float32)
    ys = []
    for ci in range(nc):
        sl = slice(ci * q, (ci + 1) * q)
        h_state, y = per_chunk(
            h_state, (x_dt[:, sl], da[:, sl], b[:, sl], c[:, sl]))
        ys.append(y)
    out = jnp.concatenate(ys, axis=1) if nc > 1 else ys[0]
    return out, h_state


def _mixer(params, x, cfg, want_cache: bool):
    dt_proj = x @ params["in_proj"].astype(x.dtype)
    z, xc, b, c, dt = _split_proj(cfg, dt_proj)
    conv_in = jnp.concatenate([xc, b, c], axis=-1)
    conv_out = causal_conv1d(conv_in, params["conv_w"], params["conv_b"])
    di, n = cfg.d_inner, cfg.ssm_state
    xc, b, c = jnp.split(conv_out, [di, di + n], axis=-1)
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    xh = xc.reshape(*xc.shape[:-1], h, p)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    y, h_final = ssd_chunked(xh, dt, params["A_log"], b, c, cfg.ssd_chunk)
    y = y + xh * params["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(*xc.shape[:-1], di)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"].astype(x.dtype)
    if not want_cache:
        return out, None
    k = params["conv_w"].shape[0]
    cache = {"conv": conv_in[:, conv_in.shape[1] - (k - 1):],
             "state": h_final}
    return out, cache


def ssd_apply(params, x, cfg):
    """Full Mamba-2 mixer (training). x: (B,S,E)."""
    return _mixer(params, x, cfg, want_cache=False)[0]


def ssd_prefill(params, x, cfg):
    """Prefill: returns (y, cache) with the post-sequence SSM/conv state."""
    return _mixer(params, x, cfg, want_cache=True)


# -- decode ---------------------------------------------------------------------


def ssd_cache_specs(cfg, batch: int) -> Dict[str, Any]:
    di, n = cfg.d_inner, cfg.ssm_state
    conv_dim = di + 2 * n
    return {
        "conv": ParamSpec((batch, cfg.conv_width - 1, conv_dim),
                          ("batch", (), "mlp"), "zeros"),
        "state": ParamSpec((batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                           ("batch", "heads", (), "state"), "zeros"),
    }


def ssd_init_cache(cfg, batch: int, dtype):
    di, n = cfg.d_inner, cfg.ssm_state
    return {"conv": jnp.zeros((batch, cfg.conv_width - 1, di + 2 * n), dtype),
            "state": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                               jnp.float32)}


def ssd_decode(params, x, cfg, cache):
    """One-token step. x: (B,1,E)."""
    dt_proj = x @ params["in_proj"].astype(x.dtype)
    z, xc, b, c, dt = _split_proj(cfg, dt_proj)
    conv_in = jnp.concatenate([xc, b, c], axis=-1)       # (B,1,C)
    window = jnp.concatenate([cache["conv"], conv_in], axis=1)
    w, bias = params["conv_w"], params["conv_b"]
    conv_out = jnp.einsum("bkc,kc->bc", window, w.astype(x.dtype)) \
        + bias.astype(x.dtype)
    conv_out = jax.nn.silu(conv_out)[:, None, :]
    di, n = cfg.d_inner, cfg.ssm_state
    xc, b, c = jnp.split(conv_out, [di, di + n], axis=-1)
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    xh = xc.reshape(-1, h, p).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))[:, 0]
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    da = jnp.exp(dt * a[None, :])                         # (B,H)
    bx = jnp.einsum("bn,bhp->bhpn", b[:, 0].astype(jnp.float32),
                    xh * dt[..., None])
    state = cache["state"] * da[..., None, None] + bx
    y = jnp.einsum("bn,bhpn->bhp", c[:, 0].astype(jnp.float32), state)
    y = y.astype(x.dtype) + xh.astype(x.dtype) * \
        params["D"].astype(x.dtype)[None, :, None]
    y = y.reshape(-1, 1, di)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"].astype(x.dtype)
    return out, {"conv": window[:, 1:], "state": state}
