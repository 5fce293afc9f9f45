"""Plain float32 reference of a Llama-architecture decoder (SmolLM-135M).

Written from the published description (HF ``LlamaForCausalLM``): token
embedding; per layer a pre-norm GQA self-attention with rotary position
embeddings (``rotate_half`` convention) and a pre-norm SiLU-gated MLP, both
added to the residual stream; a final RMSNorm; logits through the tied
embedding; mean token cross-entropy.  No kernels, no cache, no mixed
precision: every array is float32 and every matmul runs at ``highest``
precision.  Layers are stacked on a leading axis and scanned, each under
``jax.checkpoint``, so that a whole row of 2,048 tokens fits in memory.

Sizes come from the benchmark's configuration file (HF key names), never
from the program.  ``to_program`` and ``from_program`` map between this
layout and the parameter tree the program under test keeps; they move
values and do no arithmetic except the norm weights, which the program
holds as offsets from 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "layers": cfg["num_hidden_layers"], "heads": h,
            "kv": cfg["num_key_value_heads"], "hd": d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
            "std": cfg["initializer_range"]}


# -- parameters ---------------------------------------------------------------

NORMS = ("layers.attn_norm", "layers.mlp_norm", "final_norm")


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, L, hd = s["d"], s["layers"], s["hd"]
    return {
        "embed": (s["vocab"], d),
        "final_norm": (d,),
        "layers": {
            "attn_norm": (L, d),
            "wq": (L, d, s["heads"] * hd),
            "wk": (L, d, s["kv"] * hd),
            "wv": (L, d, s["kv"] * hd),
            "wo": (L, s["heads"] * hd, d),
            "mlp_norm": (L, d),
            "w_gate": (L, d, s["ff"]),
            "w_up": (L, d, s["ff"]),
            "w_down": (L, s["ff"], d),
        },
    }


def init_params(key, cfg: dict) -> dict:
    """HF Llama's init: normal(0, initializer_range) for every matrix and
    the embedding, ones for the norm weights."""
    std = sizes(cfg)["std"]
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = ".".join(p.key for p in path)
        if name in NORMS:
            out.append(jnp.ones(shape, F32))
        else:
            out.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, F32))
    return jax.tree_util.tree_unflatten(tree, out)


def to_program(p: dict, cfg: dict) -> dict:
    s = sizes(cfg)
    L, d, hd = s["layers"], s["d"], s["hd"]
    ly = p["layers"]
    return {
        "embed": {"tokens": p["embed"]},
        "blocks": {"p0": {
            "ln1": ly["attn_norm"] - 1.0,
            "attn": {
                "wq": ly["wq"].reshape(L, d, s["heads"], hd),
                "wk": ly["wk"].reshape(L, d, s["kv"], hd),
                "wv": ly["wv"].reshape(L, d, s["kv"], hd),
                "wo": ly["wo"].reshape(L, s["heads"], hd, d),
            },
            "ln2": ly["mlp_norm"] - 1.0,
            "ffn": {"w_gate": ly["w_gate"], "w_up": ly["w_up"],
                    "w_down": ly["w_down"]},
        }},
        "final_norm": p["final_norm"] - 1.0,
    }


def from_program(t: dict, cfg: dict) -> dict:
    """A program-layout tree of gradients, moments or parameter changes in
    this layout (linear: no offset is added back)."""
    s = sizes(cfg)
    L, d, hd = s["layers"], s["d"], s["hd"]
    b = t["blocks"]["p0"]
    return {
        "embed": t["embed"]["tokens"],
        "final_norm": t["final_norm"],
        "layers": {
            "attn_norm": b["ln1"],
            "wq": b["attn"]["wq"].reshape(L, d, s["heads"] * hd),
            "wk": b["attn"]["wk"].reshape(L, d, s["kv"] * hd),
            "wv": b["attn"]["wv"].reshape(L, d, s["kv"] * hd),
            "wo": b["attn"]["wo"].reshape(L, s["heads"] * hd, d),
            "mlp_norm": b["ln2"],
            "w_gate": b["ffn"]["w_gate"],
            "w_up": b["ffn"]["w_up"],
            "w_down": b["ffn"]["w_down"],
        },
    }


# -- the model ----------------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x: (B, S, heads, hd); HF Llama's rotary embedding."""
    hd, seq = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(seq, dtype=F32)[:, None] * inv[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def _layer(s):
    def layer(x, w):
        b, seq, _ = x.shape
        h = rms_norm(x, w["attn_norm"], s["eps"])
        q = (h @ w["wq"]).reshape(b, seq, s["heads"], s["hd"])
        k = (h @ w["wk"]).reshape(b, seq, s["kv"], s["hd"])
        v = (h @ w["wv"]).reshape(b, seq, s["kv"], s["hd"])
        q, k = rope(q, s["theta"]), rope(k, s["theta"])
        rep = s["heads"] // s["kv"]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(s["hd"])
        causal = jnp.tril(jnp.ones((seq, seq), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, seq, -1)
        x = x + o @ w["wo"]
        h = rms_norm(x, w["mlp_norm"], s["eps"])
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        return x, None
    return jax.checkpoint(layer)


def token_nll_sum(p: dict, tokens, labels, cfg: dict):
    """Sum over the block's tokens of -log p(label)."""
    s = sizes(cfg)
    x = p["embed"][tokens]
    x, _ = jax.lax.scan(_layer(s), x, p["layers"])
    x = rms_norm(x, p["final_norm"], s["eps"])
    logits = x @ p["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


# -- work ---------------------------------------------------------------------


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token needs, recomputation not counted.

    6 per weight of every matmul (the blocks' projections and the tied
    output head; the embedding lookup is a gather), plus causal attention:
    a query at position t scores t+1 keys and mixes t+1 values, 2 FLOPs a
    multiply-add, over heads * head_dim, averaged over the sequence, and
    three times that for forward plus backward.
    """
    s = sizes(cfg)
    d, hd = s["d"], s["hd"]
    attn_w = d * (s["heads"] * hd + 2 * s["kv"] * hd) + s["heads"] * hd * d
    mlp_w = 3 * d * s["ff"]
    matmul_w = s["layers"] * (attn_w + mlp_w) + s["vocab"] * d
    attn = s["layers"] * 3 * 2 * 2 * s["heads"] * hd * (seq_len + 1) / 2
    return 6.0 * matmul_w + attn
