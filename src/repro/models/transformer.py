"""Unified decoder-only LM over repeating block patterns.

A model is a *pattern* of block kinds — e.g. ``("global",)`` (llama-style),
``("local", "global")`` (gemma2), ``("rglru", "rglru", "local")``
(recurrentgemma), ``("ssd",)`` (mamba2), ``("moe",)`` — scanned over
``num_layers // len(pattern)`` repeats (plus an unscanned tail when the depth
is not a multiple).  Scanning keeps trace and compile time O(pattern), not
O(depth), and the stacked parameter layout ["layers", ...] is what the
elastic resharding engine moves between meshes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (ParamSpec, embed_apply, embed_specs,
                                 init_from_specs, is_spec, logical_tree,
                                 mlp_apply, mlp_specs, rms_norm,
                                 unembed_apply)

ATTN_KINDS = ("global", "local")


def stack_specs(specs, n: int):
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.logical,
                            s.init, s.scale),
        specs, is_leaf=is_spec)


# -- block definitions -------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return attn.mla_specs(cfg) if cfg.mla else attn.attention_specs(cfg)


def block_specs(cfg: ModelConfig, kind: str, dense_ff: Optional[int] = None
                ) -> Dict[str, Any]:
    e = cfg.d_model
    norm = lambda: ParamSpec((e,), ("embed",), "zeros")  # noqa: E731
    if kind in ATTN_KINDS:
        specs = {"ln1": norm(), "attn": attn_specs(cfg), "ln2": norm()}
        if cfg.family == "moe" and dense_ff is None:
            specs["ffn"] = moe_mod.moe_specs(cfg)
        else:
            specs["ffn"] = mlp_specs(cfg, d_ff=dense_ff)
        return specs
    if kind == "moe":
        return {"ln1": norm(), "attn": attn_specs(cfg), "ln2": norm(),
                "ffn": (mlp_specs(cfg, d_ff=dense_ff) if dense_ff
                        else moe_mod.moe_specs(cfg))}
    if kind == "ssd":
        return {"ln1": norm(), "mixer": ssm_mod.ssd_specs(cfg)}
    if kind == "rglru":
        return {"ln1": norm(), "mixer": rglru_mod.rglru_specs(cfg),
                "ln2": norm(), "ffn": mlp_specs(cfg)}
    raise ValueError(kind)


def block_apply(params, x, cfg: ModelConfig, kind: str, stats,
                mixer=lambda f: f):
    """One block, training/prefill path (full sequence).  ``stats``: the
    step's readings so far (``moe.zero_stats``), returned with this
    block's added.  ``mixer`` wraps the attention of an attention block
    (``CausalLM``'s rematerialisation under ``remat="nested"``)."""
    if kind in ("global", "local", "moe"):
        a_kind = "local" if kind == "local" else "global"
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if cfg.mla:
            with jax.named_scope("mla"):
                x = x + mixer(lambda p, h: attn.mla_apply(p, h, cfg))(
                    params["attn"], h)
        else:
            with jax.named_scope("attn"):
                x = x + mixer(lambda p, h: attn.attention_apply(
                    p, h, cfg, kind=a_kind))(params["attn"], h)
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if "router" in params["ffn"]:
            with jax.named_scope("moe"):
                y, layer = moe_mod.moe_apply(params["ffn"], h, cfg)
            stats = moe_mod.add_stats(stats, layer)
        else:
            with jax.named_scope("mlp"):
                y = mlp_apply(params["ffn"], h, cfg)
        return x + y, stats
    if kind == "ssd":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        with jax.named_scope("ssd"):
            return x + ssm_mod.ssd_apply(params["mixer"], h, cfg), stats
    if kind == "rglru":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        with jax.named_scope("rglru"):
            x = x + rglru_mod.rglru_mixer_apply(params["mixer"], h, cfg)
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            return x + mlp_apply(params["ffn"], h, cfg), stats
    raise ValueError(kind)


# -- block caches -------------------------------------------------------------


def block_cache_specs(cfg, kind: str, batch: int, max_len: int):
    if cfg.mla:
        return attn.mla_cache_specs(cfg, batch, max_len)
    if kind in ("global", "moe"):
        return attn.cache_specs(cfg, batch, max_len)
    if kind == "local":
        w = min(cfg.sliding_window or max_len, max_len)
        return attn.cache_specs(cfg, batch, w)
    if kind == "ssd":
        return ssm_mod.ssd_cache_specs(cfg, batch)
    if kind == "rglru":
        return rglru_mod.rglru_cache_specs(cfg, batch)
    raise ValueError(kind)


def block_decode(params, x, cfg, kind: str, cache, pos):
    if kind in ("global", "local", "moe"):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if cfg.mla:
            y, cache = attn.mla_decode(params["attn"], h, cfg, cache, pos)
        else:
            window = cfg.sliding_window if kind == "local" else None
            y, cache = attn.decode_attention(params["attn"], h, cfg, cache,
                                             pos, window=window)
        x = x + y
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if "router" in params["ffn"]:
            y, _ = moe_mod.moe_apply(params["ffn"], h, cfg)
        else:
            y = mlp_apply(params["ffn"], h, cfg)
        return x + y, cache
    if kind == "ssd":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        y, cache = ssm_mod.ssd_decode(params["mixer"], h, cfg, cache)
        return x + y, cache
    if kind == "rglru":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        y, cache = rglru_mod.rglru_decode(params["mixer"], h, cfg, cache)
        x = x + y
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        return x + mlp_apply(params["ffn"], h, cfg), cache
    raise ValueError(kind)


def block_prefill(params, x, cfg, kind: str, max_len: int):
    """Full-sequence forward that also fills the block cache."""
    if kind in ("global", "local", "moe"):
        a_kind = "local" if kind == "local" else "global"
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if cfg.mla:
            y, cache = attn.mla_prefill(params["attn"], h, cfg,
                                        cache_len=max_len)
        else:
            y, cache = attn.attention_prefill(params["attn"], h, cfg,
                                              kind=a_kind, cache_len=max_len)
        x = x + y
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if "router" in params["ffn"]:
            y, _ = moe_mod.moe_apply(params["ffn"], h, cfg)
        else:
            y = mlp_apply(params["ffn"], h, cfg)
        return x + y, cache
    if kind == "ssd":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        y, cache = ssm_mod.ssd_prefill(params["mixer"], h, cfg)
        return x + y, cache
    if kind == "rglru":
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        y, cache = rglru_mod.rglru_prefill(params["mixer"], h, cfg)
        x = x + y
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        return x + mlp_apply(params["ffn"], h, cfg), cache
    raise ValueError(kind)


# -- the model -----------------------------------------------------------------


class CausalLM:
    """Decoder-only LM (all non-encdec families)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---- parameters ----

    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        reps, tail = self._pattern_layout()
        specs: Dict[str, Any] = {"embed": embed_specs(cfg)}
        for i in range(cfg.first_dense_layers):
            specs[f"head{i}"] = block_specs(
                cfg, cfg.pattern[0],
                dense_ff=cfg.first_dense_ff or cfg.d_ff)
        if reps > 0:
            unit = {f"p{j}": block_specs(cfg, kind)
                    for j, kind in enumerate(cfg.pattern)}
            specs["blocks"] = stack_specs(unit, reps)
        for t in range(tail):
            specs[f"tail{t}"] = block_specs(cfg, cfg.pattern[t])
        specs["final_norm"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
        return specs

    def _pattern_layout(self) -> Tuple[int, int]:
        cfg = self.cfg
        n = cfg.num_layers - cfg.first_dense_layers
        return n // len(cfg.pattern), n % len(cfg.pattern)

    def init(self, key) -> Dict[str, Any]:
        return init_from_specs(key, self.specs(),
                               jnp.dtype(self.cfg.param_dtype))

    def logical(self):
        return logical_tree(self.specs())

    # ---- forward (training / prefill trunk) ----

    def _remat(self, fn):
        """``fn`` (a layer, or the scan's repetition of the pattern) under
        the configuration's rematerialisation policy."""
        cfg = self.cfg
        if cfg.remat == "none":
            return fn
        policy = (jax.checkpoint_policies.checkpoint_dots
                  if cfg.remat == "dots" else
                  jax.checkpoint_policies.nothing_saveable)
        return jax.checkpoint(fn, policy=policy, prevent_cse=False)

    def _mixer(self, fn):
        """The attention within a rematerialised layer: under
        ``remat="nested"`` rematerialised once more, in its own backward
        pass, so that its intermediates (MLA's attention core at 8k) are
        not live through the feed-forward's backward pass."""
        if self.cfg.remat != "nested":
            return fn
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_unit(self):
        """The layer scan's body, one repetition of the pattern under the
        configuration's rematerialisation:
        ``((x, stats), unit_params) -> ((x, stats), None)``."""
        cfg = self.cfg

        def unit(carry, unit_params):
            x, stats = carry
            for j, kind in enumerate(cfg.pattern):
                x, stats = block_apply(unit_params[f"p{j}"], x, cfg, kind,
                                       stats, mixer=self._mixer)
            return (x, stats), None
        return self._remat(unit)

    def _trunk(self, params, x):
        cfg = self.cfg
        reps, tail = self._pattern_layout()
        stats = moe_mod.zero_stats(cfg)

        def one(kind):
            return self._remat(lambda carry, p: block_apply(
                p, carry[0], cfg, kind, carry[1], mixer=self._mixer))
        for i in range(cfg.first_dense_layers):
            x, stats = one(cfg.pattern[0])((x, stats), params[f"head{i}"])
        if reps > 0:
            (x, stats), _ = jax.lax.scan(self.scan_unit(), (x, stats),
                                         params["blocks"])
        for t in range(tail):
            x, stats = one(cfg.pattern[t])((x, stats), params[f"tail{t}"])
        return rms_norm(x, params["final_norm"], cfg.norm_eps), stats

    def _hidden(self, params, tokens, extra_embeds=None):
        """Embedding and trunk: the final hidden states and the step's
        readings (``moe.zero_stats``)."""
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens, cfg)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        return self._trunk(params, x)

    def forward(self, params, tokens, extra_embeds=None):
        """tokens: (B, S_text). extra_embeds: (B, S_front, E) modality stub
        prepended to the sequence (VLM patches / audio frames)."""
        x, stats = self._hidden(params, tokens, extra_embeds)
        return unembed_apply(params["embed"], x, self.cfg), stats["aux"]

    def loss(self, params, batch):
        """batch: tokens (B,S), labels (B,S) [-1 = masked], optional
        frontend embeds.  Returns the loss with the balance loss added,
        and its parts: ``ce``, ``aux`` and ``counters``, the MoE layers'
        counters of ``moe.zero_stats`` (none for a model without
        experts)."""
        cfg = self.cfg
        labels = batch["labels"]
        mask = (labels >= 0)
        labels = jnp.maximum(labels, 0)
        denom = jnp.maximum(mask.sum(), 1)

        fr = batch.get("frontend")
        x, stats = self._hidden(params, batch["tokens"], fr)
        aux = stats.pop("aux")
        with jax.named_scope("lm_head_loss"):
            logits = unembed_apply(params["embed"], x, cfg)
            if fr is not None:   # frontend positions carry no labels
                logits = logits[:, fr.shape[1]:]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(logp, labels[..., None],
                                     axis=-1)[..., 0]
            loss = -(ll * mask).sum() / denom
        return loss + aux, {"ce": loss, "aux": aux, "counters": stats}

    # ---- serving ----

    def cache_specs(self, batch: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        reps, tail = self._pattern_layout()
        out: Dict[str, Any] = {}
        for i in range(cfg.first_dense_layers):
            out[f"head{i}"] = block_cache_specs(cfg, cfg.pattern[0],
                                                batch, max_len)
        if reps > 0:
            unit = {f"p{j}": block_cache_specs(cfg, kind, batch, max_len)
                    for j, kind in enumerate(cfg.pattern)}
            out["blocks"] = stack_specs(unit, reps)
        for t in range(tail):
            out[f"tail{t}"] = block_cache_specs(cfg, cfg.pattern[t],
                                                batch, max_len)
        return out

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        specs = self.cache_specs(batch, max_len)

        def build(path, spec):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            if name == "pos":
                return jnp.full(spec.shape, -1, jnp.int32)
            if name in ("state", "h"):
                return jnp.zeros(spec.shape, jnp.float32)
            return jnp.zeros(spec.shape, dtype)
        return jax.tree_util.tree_map_with_path(build, specs,
                                                is_leaf=is_spec)

    def prefill(self, params, tokens, max_len: int, extra_embeds=None):
        """Run the full prompt, returning (last-position logits, cache)."""
        cfg = self.cfg
        x = embed_apply(params["embed"], tokens, cfg)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        cache: Dict[str, Any] = {}
        for i in range(cfg.first_dense_layers):
            x, cache[f"head{i}"] = block_prefill(
                params[f"head{i}"], x, cfg, cfg.pattern[0], max_len)
        reps, tail = self._pattern_layout()
        if reps > 0:
            def unit(x, unit_params):
                caches = {}
                for j, kind in enumerate(cfg.pattern):
                    x, caches[f"p{j}"] = block_prefill(
                        unit_params[f"p{j}"], x, cfg, kind, max_len)
                return x, caches
            x, cache["blocks"] = jax.lax.scan(unit, x, params["blocks"])
        for t in range(tail):
            x, cache[f"tail{t}"] = block_prefill(
                params[f"tail{t}"], x, cfg, cfg.pattern[t], max_len)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed_apply(params["embed"], x[:, -1:], cfg)
        return logits, cache

    def decode_step(self, params, cache, token, pos):
        """token: (B, 1) int32; pos: scalar int32. Returns (logits, cache)."""
        cfg = self.cfg
        x = embed_apply(params["embed"], token, cfg)
        for i in range(cfg.first_dense_layers):
            x, cache[f"head{i}"] = block_decode(
                params[f"head{i}"], x, cfg, cfg.pattern[0],
                cache[f"head{i}"], pos)
        reps, tail = self._pattern_layout()
        if reps > 0:
            def unit(x, inp):
                unit_params, unit_cache = inp
                new_cache = {}
                for j, kind in enumerate(cfg.pattern):
                    x, new_cache[f"p{j}"] = block_decode(
                        unit_params[f"p{j}"], x, cfg, kind,
                        unit_cache[f"p{j}"], pos)
                return x, new_cache
            x, cache["blocks"] = jax.lax.scan(
                unit, x, (params["blocks"], cache["blocks"]))
        for t in range(tail):
            x, cache[f"tail{t}"] = block_decode(
                params[f"tail{t}"], x, cfg, cfg.pattern[t],
                cache[f"tail{t}"], pos)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed_apply(params["embed"], x, cfg)
        return logits, cache
