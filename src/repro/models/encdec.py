"""Encoder-decoder LM (seamless-m4t backbone: speech/text enc -> text dec).

The modality frontend is a stub: ``input_specs`` supplies precomputed frame
embeddings (B, S_enc, E).  Encoder = bidirectional attention blocks; decoder
= causal self-attention + cross-attention + MLP.  Decode keeps a self-
attention KV cache plus a precomputed cross-attention KV (from the encoder
output), as a production seq2seq server would.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.config import ModelConfig
from repro.models.layers import (ParamSpec, embed_apply, embed_specs,
                                 init_from_specs, logical_tree, mlp_apply,
                                 mlp_specs, rms_norm, unembed_apply)
from repro.models.transformer import stack_specs


def _norm(cfg):
    return ParamSpec((cfg.d_model,), ("embed",), "zeros")


def enc_block_specs(cfg) -> Dict[str, Any]:
    return {"ln1": _norm(cfg), "attn": attn.attention_specs(cfg),
            "ln2": _norm(cfg), "ffn": mlp_specs(cfg)}


def dec_block_specs(cfg) -> Dict[str, Any]:
    return {"ln1": _norm(cfg), "self_attn": attn.attention_specs(cfg),
            "ln_x": _norm(cfg), "cross_attn": attn.cross_attention_specs(cfg),
            "ln2": _norm(cfg), "ffn": mlp_specs(cfg)}


def enc_block_apply(params, x, cfg):
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    x = x + attn.attention_apply(params["attn"], h, cfg, kind="global",
                                 causal=False)
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    return x + mlp_apply(params["ffn"], h, cfg)


def dec_block_apply(params, x, enc_out, cfg):
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    x = x + attn.attention_apply(params["self_attn"], h, cfg, kind="global")
    h = rms_norm(x, params["ln_x"], cfg.norm_eps)
    x = x + attn.attention_apply(params["cross_attn"], h, cfg, kind="cross",
                                 x_kv=enc_out)
    h = rms_norm(x, params["ln2"], cfg.norm_eps)
    return x + mlp_apply(params["ffn"], h, cfg)


class EncDecLM:
    def __init__(self, cfg: ModelConfig):
        assert cfg.enc_layers > 0
        self.cfg = cfg

    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        return {
            "embed": embed_specs(cfg),
            "enc_in": ParamSpec((cfg.d_model, cfg.d_model),
                                ("frontend", "embed")),
            "enc_blocks": stack_specs(enc_block_specs(cfg), cfg.enc_layers),
            "enc_norm": _norm(cfg),
            "dec_blocks": stack_specs(dec_block_specs(cfg), cfg.num_layers),
            "final_norm": _norm(cfg),
        }

    def init(self, key):
        return init_from_specs(key, self.specs(),
                               jnp.dtype(self.cfg.param_dtype))

    def logical(self):
        return logical_tree(self.specs())

    def encode(self, params, frames):
        """frames: (B, S_enc, E) stub frontend embeddings."""
        cfg = self.cfg
        x = (frames.astype(jnp.dtype(cfg.dtype))
             @ params["enc_in"].astype(jnp.dtype(cfg.dtype)))

        def body(x, blk):
            return enc_block_apply(blk, x, cfg), None
        if cfg.remat != "none":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def forward(self, params, frames, tokens):
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        x = embed_apply(params["embed"], tokens, cfg)

        def body(x, blk):
            return dec_block_apply(blk, x, enc_out, cfg), None
        if cfg.remat != "none":
            body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return unembed_apply(params["embed"], x, cfg), jnp.zeros(
            (), jnp.float32)

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch["frontend"],
                                   batch["tokens"])
        labels = batch["labels"]
        mask = labels >= 0
        labels = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)
        return loss, {"ce": loss, "aux": aux}

    # ---- serving ----

    def cache_specs(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        unit = {
            "self": attn.cache_specs(cfg, batch, max_len),
            "cross_k": ParamSpec((batch, max_len, kv, hd),
                                 ("batch", "kv_seq", "kv_heads", "head_dim"),
                                 "zeros"),
            "cross_v": ParamSpec((batch, max_len, kv, hd),
                                 ("batch", "kv_seq", "kv_heads", "head_dim"),
                                 "zeros"),
        }
        return {"dec_blocks": stack_specs(unit, cfg.num_layers)}

    def prefill(self, params, frames, tokens, max_len: int):
        """Encode + run the decoder prompt, producing decode caches."""
        cfg = self.cfg
        enc_out = self.encode(params, frames)
        x = embed_apply(params["embed"], tokens, cfg)

        def body(x, blk):
            h = rms_norm(x, blk["ln1"], cfg.norm_eps)
            y, self_cache = attn.attention_prefill(
                blk["self_attn"], h, cfg, kind="global", cache_len=max_len)
            x = x + y
            h = rms_norm(x, blk["ln_x"], cfg.norm_eps)
            dt = x.dtype
            ck = jnp.einsum("bse,ehd->bshd", enc_out,
                            blk["cross_attn"]["wk"].astype(dt))
            cv = jnp.einsum("bse,ehd->bshd", enc_out,
                            blk["cross_attn"]["wv"].astype(dt))
            x = x + attn.attention_apply(blk["cross_attn"], h, cfg,
                                         kind="cross", x_kv=enc_out)
            h = rms_norm(x, blk["ln2"], cfg.norm_eps)
            x = x + mlp_apply(blk["ffn"], h, cfg)
            return x, {"self": self_cache, "cross_k": ck, "cross_v": cv}

        x, caches = jax.lax.scan(body, x, params["dec_blocks"])
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed_apply(params["embed"], x[:, -1:], cfg)
        return logits, {"dec_blocks": caches}

    def decode_step(self, params, cache, token, pos):
        cfg = self.cfg
        x = embed_apply(params["embed"], token, cfg)

        def body(x, inp):
            blk, c = inp
            h = rms_norm(x, blk["ln1"], cfg.norm_eps)
            y, self_cache = attn.decode_attention(blk["self_attn"], h, cfg,
                                                  c["self"], pos)
            x = x + y
            h = rms_norm(x, blk["ln_x"], cfg.norm_eps)
            x = x + _cross_decode(blk["cross_attn"], h, cfg,
                                  c["cross_k"], c["cross_v"])
            h = rms_norm(x, blk["ln2"], cfg.norm_eps)
            x = x + mlp_apply(blk["ffn"], h, cfg)
            return x, {"self": self_cache, "cross_k": c["cross_k"],
                       "cross_v": c["cross_v"]}

        x, caches = jax.lax.scan(body, x, (params["dec_blocks"],
                                           cache["dec_blocks"]))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed_apply(params["embed"], x, cfg)
        return logits, {"dec_blocks": caches}


def _cross_decode(params, x, cfg, ck, cv):
    """Single-query cross attention over precomputed encoder KV."""
    import math
    b = x.shape[0]
    dt = x.dtype
    q = jnp.einsum("bse,ehd->bshd", x, params["wq"].astype(dt))
    kvh, hd = ck.shape[2], ck.shape[3]
    g = cfg.num_heads // kvh
    qg = q.reshape(b, 1, kvh, g, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck).astype(jnp.float32)
    p = jax.nn.softmax(logits / math.sqrt(hd), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(dt), cv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, cfg.num_heads, hd)
    return jnp.einsum("bshd,hde->bse", out, params["wo"].astype(dt))
