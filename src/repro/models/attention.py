"""Attention: GQA projections, multi-head latent attention (MLA), exact-FLOPs
chunked causal attention (XLA path), local sliding windows, logit softcaps,
qk-norm, and KV caches.

Implementation notes (TPU adaptation):

- The training/prefill XLA path is *chunked online-softmax* attention: the
  query axis is split into chunks (python-unrolled, so each chunk's key
  prefix is a static slice) and each chunk scans its key prefix with a
  running (max, sum, acc) — flash attention expressed in jnp.  This keeps
  the compiled HLO at the exact causal FLOP count (no wasted upper-triangle
  work) and O(chunk²) live memory.  GQA attention runs it on every platform.
- MLA's causal core runs JAX's fused splash-attention Pallas kernel when
  the step is traced for a TPU (:func:`fused_causal_attention`), and
  :func:`chunked_attention` elsewhere: the platform is that of the mesh the
  trainer traces the step under (``core.sharding.context_mesh``), or the
  default backend outside one.  The kernel keeps the logits and the
  softmax in fp32 in VMEM and skips the blocks the causal mask empties.
- Local (sliding-window) layers attend a static window around each query
  chunk; decode-time local layers use a **ring-buffer cache** of window
  size, which is what keeps hybrid archs O(window) at 500k tokens.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)

from repro.core.sharding import TP_DP_RULES, context_mesh
from repro.models.layers import (ParamSpec, apply_rope, rms_norm, softcap,
                                 yarn_mscale)

NEG_INF = -2.0 ** 30


def attention_specs(cfg) -> Dict[str, Any]:
    e, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((e, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((e, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((e, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, e), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
    return specs


def cross_attention_specs(cfg) -> Dict[str, Any]:
    return attention_specs(cfg)


def _project_qkv(params, x, cfg, positions, rope: bool = True,
                 x_kv=None):
    dt = x.dtype
    x_kv = x if x_kv is None else x_kv
    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(dt))
    k = jnp.einsum("bse,ehd->bshd", x_kv, params["wk"].astype(dt))
    v = jnp.einsum("bse,ehd->bshd", x_kv, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_chunk(q, k, v, mask, cfg, scale, state=None):
    """Online-softmax update of one (q-chunk, kv-chunk) pair.

    q: (B, Sq, KV, G, D); k: (B, Sk, KV, D); v: (B, Sk, KV, Dv);
    mask: (Sq, Sk) or None.  state: (m, l, acc) running max / normalizer /
    weighted accumulator.
    """
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_logit_softcap)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    m_new = jnp.max(logits, axis=-1)                       # (B,KV,G,Sq)
    if state is not None:
        m_prev, l_prev, acc_prev = state
        m_new = jnp.maximum(m_prev, m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), v)
    if state is not None:
        corr = jnp.exp(m_prev - m_new)
        l_new = l_new + corr * l_prev
        pv = pv + corr[..., None].astype(q.dtype) * acc_prev
    return m_new, l_new, pv


def _finish(l, acc):
    return (acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype))


def chunked_attention(q, k, v, cfg, *, causal: bool, window: Optional[int],
                      scale: Optional[float] = None):
    """Exact-FLOPs chunked attention.

    q: (B, S, H, D) -> grouped (B, S, KV, G, D); k: (B, S, KV, D); v: (B,
    S, KV, Dv), whose width may differ from the query's.  Logits are scaled
    by ``scale`` (default 1/sqrt(D)).  The query axis is python-unrolled in
    chunks; each chunk attends a *static* key slice (its causal prefix, or
    its sliding window), with an inner scan over kv chunks carrying the
    online-softmax state.
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    kvh = k.shape[2]
    g = h // kvh
    q = q.reshape(b, s, kvh, g, d)
    c = min(cfg.attn_chunk, s)
    while s % c:
        c //= 2
    n_chunks = s // c

    def one_chunk(q_i, k_slice, v_slice, i, lo, hi):
        """One query chunk against its static key slice.

        The kv loop is python-unrolled (not lax.scan) so every chunk-pair's
        FLOPs appear explicitly in the HLO — XLA's cost_analysis counts
        while-loop bodies only once, which would hide the causal-prefix
        work from the roofline.  Masks are only applied on the diagonal /
        window-edge pairs, so the compiled FLOP count is the exact causal
        cost.
        """
        q_pos = i * c + jnp.arange(c)
        n_kv = (hi - lo) // c
        state = None
        for j in range(n_kv):
            kk = k_slice[:, j * c:(j + 1) * c]
            vv = v_slice[:, j * c:(j + 1) * c]
            kv_lo = lo + j * c
            mask = None
            # mask only where the chunk-pair can be partially invalid:
            # the causal diagonal and the sliding-window edge.
            diag = causal and kv_lo + c > i * c
            edge = window is not None and kv_lo < i * c + c - window
            if diag or edge:
                kv_pos = kv_lo + jnp.arange(c)
                mask = jnp.ones((c, c), bool)
                if causal:
                    mask &= q_pos[:, None] >= kv_pos[None, :]
                if window is not None:
                    mask &= q_pos[:, None] - kv_pos[None, :] < window
            m, l, acc = _sdpa_chunk(q_i, kk, vv, mask, cfg, scale, state)
            state = (m, l, acc)
        return _finish(state[1], state[2])

    # Remat each q-chunk: the backward pass recomputes the chunk's online
    # softmax instead of saving per-kv-step residuals — this is what keeps
    # the train-time activation footprint O(chunk^2), like the TPU kernel.
    one_chunk_ckpt = jax.checkpoint(
        one_chunk, policy=jax.checkpoint_policies.nothing_saveable,
        prevent_cse=False, static_argnums=(3, 4, 5))

    outs = []
    for i in range(n_chunks):
        q_i = q[:, i * c:(i + 1) * c]
        if causal:
            lo = 0 if window is None else max(0, (i * c + c) - window - c + 1)
            lo = (lo // c) * c                 # static prefix chunk start
            hi = (i + 1) * c
        else:
            lo, hi = 0, s
        fn = one_chunk_ckpt if n_chunks > 1 else one_chunk
        outs.append(fn(q_i, k[:, lo:hi], v[:, lo:hi], i, lo, hi))
    out = jnp.concatenate(outs, axis=-2) if len(outs) > 1 else outs[0]
    # (B,KV,G,S,Dv) -> (B,S,H,Dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, v.shape[-1])
    return out


# Query and key/value block of the fused kernel, in its forward, dq and dkv
# kernels alike (the largest that divides the sequence, at most this).  The
# kernel takes sequences of whole 128-wide lanes.  On a v5e at 2 x 8,192
# tokens, 16 heads, 192/128, forward and backward take 31.3 ms at 1,024,
# 34.6 ms at 512 and 57.3 ms at 256.
SPLASH_BLOCK = 1024
SPLASH_LANES = 128


@functools.lru_cache(maxsize=None)
def _splash_kernel(heads: int, seq: int, block: int, interpret: bool):
    """The kernel and its block masks, made once; concrete even when first
    asked for inside a trace, so later traces may share them."""
    mask = splash_mask.MultiHeadMask(
        [splash_mask.CausalMask((seq, seq))] * heads)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=sizes,
                                      head_shards=1, q_seq_shards=1,
                                      interpret=interpret)


def fused_causal_attention(q, k, v, scale: float, *, mesh=None,
                           block: int = SPLASH_BLOCK,
                           interpret: bool = False):
    """Causal ``softmax(q kᵀ · scale) v`` in one Pallas kernel (JAX's
    splash attention), forward and backward.

    q, k: (B, S, H, D); v: (B, S, H, Dv), whose width may differ from the
    query's -> (B, S, H, Dv); S a multiple of ``SPLASH_LANES``.  The
    kernel takes no scale: q is scaled in fp32 and rounded back to its
    dtype, the one rounding every activation has.  On a mesh of more than
    one device the kernel runs per shard of the batch (``shard_map``),
    heads and sequence whole.
    """
    b, s, h, _ = q.shape
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = _splash_kernel(h, s, math.gcd(block, s), interpret)

    def core(q, k, v):
        def heads_first(x):
            return x.transpose(0, 2, 1, 3)
        out = jax.vmap(kernel)(heads_first(q), heads_first(k),
                               heads_first(v))
        return heads_first(out)

    if mesh is not None and mesh.size > 1:
        spec = TP_DP_RULES.spec_for(("batch", None, None, None), q.shape,
                                    mesh)
        core = jax.shard_map(core, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)
    return core(q, k, v)


# -- KV cache ------------------------------------------------------------------


def cache_specs(cfg, batch: int, length: int) -> Dict[str, Any]:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": ParamSpec((batch, length, kv, hd),
                       ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
        "v": ParamSpec((batch, length, kv, hd),
                       ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
        "pos": ParamSpec((length,), ("kv_seq",), "zeros"),
    }


def init_cache(cfg, batch: int, length: int, dtype):
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, length, kv, hd), dtype),
            "v": jnp.zeros((batch, length, kv, hd), dtype),
            "pos": jnp.full((length,), -1, jnp.int32)}


def decode_attention(params, x, cfg, cache, pos, *,
                     window: Optional[int] = None):
    """One-token decode: update cache at ``pos`` (ring-buffered for local
    windows) and attend over it.  x: (B, 1, E); pos: scalar int32."""
    b = x.shape[0]
    length = cache["k"].shape[1]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions)
    slot = pos % length    # ring buffer (global caches: length == max_seq)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    pos_arr = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0)
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos_arr}

    kvh, hd = k.shape[2], k.shape[3]
    g = cfg.num_heads // kvh
    qg = q.reshape(b, 1, kvh, g, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32)
    logits = softcap(logits * scale, cfg.attn_logit_softcap)
    valid = (pos_arr >= 0) & (pos_arr <= pos)
    if window is not None:
        valid &= pos_arr > pos - window
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(x.dtype), v_cache)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, cfg.num_heads, hd)
    y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))
    return y, new_cache


def attention_apply(params, x, cfg, *, kind: str = "global",
                    positions=None, x_kv=None, causal: bool = True):
    """Training/prefill attention.  kind: "global" | "local" | "cross"."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    rope = kind != "cross"
    q, k, v = _project_qkv(params, x, cfg, positions, rope=rope, x_kv=x_kv)
    window = cfg.sliding_window if kind == "local" else None
    out = chunked_attention(q, k, v, cfg,
                            causal=causal and kind != "cross",
                            window=window)
    return jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))


def attention_prefill(params, x, cfg, *, kind: str = "global",
                      cache_len: int):
    """Full-sequence attention that also returns the filled KV cache.

    Global layers keep all S positions (padded up to ``cache_len``); local
    layers keep the trailing ``window`` positions in ring-buffer order so
    that subsequent :func:`decode_attention` steps continue seamlessly.
    """
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, x, cfg, positions)
    window = cfg.sliding_window if kind == "local" else None
    out = chunked_attention(q, k, v, cfg, causal=True, window=window)
    y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))

    if kind == "local":
        # ring buffer of the window size (this is what keeps hybrid archs
        # O(window) at 500k tokens); position p lives at slot p % w.
        w = min(window or cache_len, cache_len)
        m = min(s, w)
        slots = (jnp.arange(s - m, s) % w).astype(jnp.int32)
        kvh, hd = k.shape[2], k.shape[3]
        k_keep = jnp.zeros((b, w, kvh, hd), k.dtype).at[:, slots].set(
            k[:, s - m:])
        v_keep = jnp.zeros((b, w, kvh, hd), v.dtype).at[:, slots].set(
            v[:, s - m:])
        pos = jnp.full((w,), -1, jnp.int32).at[slots].set(
            jnp.arange(s - m, s, dtype=jnp.int32))
    else:
        length = cache_len
        pad = length - s
        k_keep = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_keep = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
    return y, {"k": k_keep, "v": v_keep, "pos": pos}


# -- multi-head latent attention (DeepSeek-V2) ------------------------------------


def mla_specs(cfg) -> Dict[str, Any]:
    """HF ``DeepseekV2Attention`` without a query latent (``q_lora_rank``
    null): ``wq`` is ``q_proj``, ``wkv_a`` ``kv_a_proj_with_mqa`` (the latent
    and the shared rotary key), ``kv_norm`` ``kv_a_layernorm`` (an offset
    from 1), ``wkv_b`` ``kv_b_proj`` (each head's key part and value), ``wo``
    ``o_proj``."""
    e, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((e, h, nope + rope), ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((e, r + rope), ("embed", "kv_latent")),
        "kv_norm": ParamSpec((r,), ("kv_latent",), init="zeros"),
        "wkv_b": ParamSpec((r, h, nope + vd),
                           ("kv_latent", "heads", "head_dim")),
        "wo": ParamSpec((h, vd, e), ("heads", "head_dim", "embed")),
    }


def mla_scale(cfg) -> float:
    """Softmax scale: 1/sqrt(query-key width), times YaRN's mscale of
    ``mscale_all_dim`` squared where the configuration sets it."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    ys = cfg.rope_scaling
    if ys and ys.get("mscale_all_dim"):
        m = yarn_mscale(ys["factor"], ys["mscale_all_dim"])
        scale *= m * m
    return scale


def _mla_rope(x, positions, cfg):
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling,
                      interleaved=True)


def _mla_query(params, x, cfg, positions):
    """Per head: the query's plain part and its rotated part."""
    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(x.dtype))
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], _mla_rope(q[..., nope:], positions, cfg)


def _mla_latent(params, x, cfg, positions):
    """What the cache holds a token: the normalised latent (B, S, r) and
    the rotated key shared by the heads (B, S, rope)."""
    ckv = x @ params["wkv_a"].astype(x.dtype)
    r = cfg.kv_lora_rank
    c = rms_norm(ckv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(ckv[..., None, r:], positions, cfg)[:, :, 0]
    return c, k_pe


def _traced_platform(mesh) -> str:
    """The platform the code is traced for: that of the mesh in the
    sharding context, else the default backend's."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def _mla_forward(params, x, cfg):
    """Full-sequence causal MLA: the output and the cache's two parts."""
    b, s, _ = x.shape
    h, nope = cfg.num_heads, cfg.qk_nope_head_dim
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q_nope, q_pe = _mla_query(params, x, cfg, positions)
    c, k_pe = _mla_latent(params, x, cfg, positions)
    kv = jnp.einsum("bsr,rhd->bshd", c, params["wkv_b"].astype(x.dtype))
    k_pe_heads = jnp.broadcast_to(k_pe[:, :, None],
                                  (b, s, h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe_heads], axis=-1)
    mesh = context_mesh()
    if _traced_platform(mesh) == "tpu" and s % SPLASH_LANES == 0:
        out = fused_causal_attention(q, k, kv[..., nope:], mla_scale(cfg),
                                     mesh=mesh)
    else:
        out = chunked_attention(q, k, kv[..., nope:], cfg, causal=True,
                                window=None, scale=mla_scale(cfg))
    y = jnp.einsum("bshd,hde->bse", out, params["wo"].astype(x.dtype))
    return y, c, k_pe


def mla_apply(params, x, cfg):
    """Training path: x (B, S, E) -> (B, S, E)."""
    return _mla_forward(params, x, cfg)[0]


def mla_cache_specs(cfg, batch: int, length: int) -> Dict[str, Any]:
    return {
        "kv": ParamSpec((batch, length, cfg.kv_lora_rank),
                        ("batch", "kv_seq", "kv_latent"), "zeros"),
        "k_pe": ParamSpec((batch, length, cfg.qk_rope_head_dim),
                          ("batch", "kv_seq", "head_dim"), "zeros"),
        "pos": ParamSpec((length,), ("kv_seq",), "zeros"),
    }


def mla_prefill(params, x, cfg, *, cache_len: int):
    """Full-sequence MLA that also returns the latent cache, padded to
    ``cache_len``."""
    s = x.shape[1]
    y, c, k_pe = _mla_forward(params, x, cfg)
    pad = ((0, 0), (0, cache_len - s), (0, 0))
    pos = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                           jnp.full((cache_len - s,), -1, jnp.int32)])
    return y, {"kv": jnp.pad(c, pad), "k_pe": jnp.pad(k_pe, pad), "pos": pos}


def mla_decode(params, x, cfg, cache, pos):
    """One-token decode through the latent cache.  The key and value
    up-projection is absorbed: each head's query is mapped into the latent,
    scored against the cached latents and shared rotary keys, and the
    latent mix is mapped to the head's value afterwards.  x: (B, 1, E)."""
    b, dt = x.shape[0], x.dtype
    nope = cfg.qk_nope_head_dim
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_pe = _mla_query(params, x, cfg, positions)
    c, k_pe = _mla_latent(params, x, cfg, positions)
    slot = pos % cache["kv"].shape[1]
    new_cache = {
        "kv": jax.lax.dynamic_update_slice_in_dim(cache["kv"], c, slot, 1),
        "k_pe": jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe,
                                                    slot, 1),
        "pos": jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((1,), pos, jnp.int32), slot, 0)}
    wkv_b = params["wkv_b"].astype(dt)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wkv_b[..., :nope])
    logits = (jnp.einsum("bqhr,bsr->bhqs", q_lat, new_cache["kv"])
              + jnp.einsum("bqhd,bsd->bhqs", q_pe, new_cache["k_pe"]))
    logits = logits.astype(jnp.float32) * mla_scale(cfg)
    pos_arr = new_cache["pos"]
    valid = (pos_arr >= 0) & (pos_arr <= pos)
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(dt)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", p, new_cache["kv"])
    out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wkv_b[..., nope:])
    y = jnp.einsum("bqhd,hde->bqe", out, params["wo"].astype(dt))
    return y, new_cache
