"""Model primitives: parameter specs, norms, RoPE, MLP, embeddings.

Parameters are declared as :class:`ParamSpec` trees (shape + logical axes +
initializer); a single spec tree drives initialization, sharding resolution
and ``eval_shape`` — so the three can never diverge.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    logical: tuple                 # logical axis names, same rank as shape
    init: str = "normal"           # normal | zeros | ones | lru_a
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_param(key, spec: ParamSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "lru_a":
        # RG-LRU "a" parameter: recurrence gate init so that softplus-based
        # decay starts near 0.9–0.999 (Griffin §2.4).
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        val = jnp.log(jnp.expm1(-jnp.log(u) * 8.0))  # inverse softplus
        return val.astype(dtype)
    fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[0], 1)
    std = spec.scale / math.sqrt(fan_in)
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def init_from_specs(key, specs, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    params = [init_param(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, params)


def logical_tree(specs):
    return jax.tree.map(lambda s: s.logical, specs, is_leaf=is_spec)


def shape_tree(specs):
    return jax.tree.map(lambda s: s.shape, specs, is_leaf=is_spec)


def param_bytes(specs, bytes_per_el: int = 4) -> int:
    return sum(int(np.prod(s.shape)) * bytes_per_el
               for s in jax.tree.leaves(specs, is_leaf=is_spec))


# -- functional layers ---------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6, zero_centered: bool = True):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if zero_centered else scale
    return (x * w.astype(jnp.float32)).astype(dt)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def softcap(x, cap):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# -- RoPE ------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention scale for a context stretched ``factor`` times (HF
    DeepSeek-V2 ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float,
                         original_len: int) -> float:
    """The rotary dimension whose wavelength turns ``rotations`` times over
    the original context."""
    return (dim * math.log(original_len / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_frequencies(head_dim: int, theta: float, scaling=None):
    """Inverse frequencies of the rotary pairs.  With YaRN ``scaling`` (the
    HF ``rope_scaling`` dict) a linear ramp over the correction range of
    ``beta_fast``..``beta_slow`` blends the plain frequency (fast pairs)
    into the frequency divided by ``factor`` (slow pairs), as HF
    ``DeepseekV2YarnRotaryEmbedding`` does."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    orig = scaling["original_max_position_embeddings"]
    low = max(math.floor(_yarn_correction_dim(
        scaling["beta_fast"], head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(
        scaling["beta_slow"], head_dim, theta, orig)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inter = freqs / scaling["factor"]
    return inter * ramp + freqs * (1.0 - ramp)


def rope_mscale(scaling) -> float:
    """The factor on YaRN's cos and sin: mscale over mscale_all_dim, each
    through ``yarn_mscale``; 1 for plain rope."""
    if scaling is None:
        return 1.0
    f = scaling["factor"]
    return (yarn_mscale(f, scaling.get("mscale", 1.0))
            / yarn_mscale(f, scaling.get("mscale_all_dim", 0.0)))


def apply_rope(x, positions, theta: float = 10000.0, scaling=None,
               interleaved: bool = False):
    """x: (..., seq, heads, head_dim); positions: (..., seq).

    ``interleaved``: the rotary pairs are adjacent dimensions (2i, 2i+1),
    and the output holds the pairs' first members, then their second, as
    HF DeepSeek-V2's ``apply_rotary_pos_emb`` de-interleaves them before
    ``rotate_half``; otherwise pair i is (i, i + head_dim / 2)."""
    freqs = rope_frequencies(x.shape[-1], theta, scaling)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                                 # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    mscale = rope_mscale(scaling)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- gated MLP ---------------------------------------------------------------------


def mlp_specs(cfg, d_ff=None) -> Dict[str, Any]:
    e, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((e, f), ("embed", "mlp")),
        "w_up": ParamSpec((e, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, e), ("mlp", "embed")),
    }


def mlp_apply(params, x, cfg):
    act = act_fn(cfg.act)
    h = act(x @ params["w_gate"].astype(x.dtype)) * \
        (x @ params["w_up"].astype(x.dtype))
    return h @ params["w_down"].astype(x.dtype)


# -- embeddings ----------------------------------------------------------------------


def embed_specs(cfg) -> Dict[str, Any]:
    # "table_embed" (not "embed"): the token-embedding gather reshards
    # catastrophically under FSDP embed-dim sharding, so the table stays
    # vocab-sharded (model axis) with its embed dim replicated.
    specs = {"tokens": ParamSpec((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "table_embed"))}
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("table_embed", "vocab"))
    return specs


def embed_apply(params, tokens, cfg):
    x = params["tokens"].astype(jnp.dtype(cfg.dtype))[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed_apply(params, x, cfg):
    if cfg.tie_embeddings:
        logits = x @ params["tokens"].astype(x.dtype).T
    else:
        logits = x @ params["unembed"].astype(x.dtype)
    return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
