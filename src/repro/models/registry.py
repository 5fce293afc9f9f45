"""Model registry: config -> model instance + reduced smoke configs."""
from __future__ import annotations

import dataclasses

from repro.models.config import ModelConfig
from repro.models.encdec import EncDecLM
from repro.models.transformer import CausalLM


def build_model(cfg: ModelConfig):
    if cfg.family == "encdec":
        return EncDecLM(cfg)
    return CausalLM(cfg)


def get_model(name: str):
    from repro.configs import get_config      # lazy: configs import models
    cfg = get_config(name)
    return build_model(cfg), cfg


def list_archs():
    from repro.configs import list_archs as _la
    return _la()


def reduced_config(cfg: ModelConfig, *, layers: int = None,
                   vocab: int = 2048) -> ModelConfig:
    """Tiny same-family config for CPU smoke tests.

    Keeps the *structure* (pattern, GQA ratio, qk_norm, softcaps, MoE
    top-k and a held share of the experts, latent attention, SSD/RG-LRU
    mixers, frontend) while shrinking width/depth/vocab.
    """
    n_pat = len(cfg.pattern)
    depth = layers if layers is not None else max(
        2 * n_pat, n_pat + cfg.first_dense_layers + 1)
    heads = max(min(cfg.num_heads, 4), 1) if cfg.num_heads else 0
    kv = max(1, heads // max(cfg.q_per_kv, 1)) if heads else 0
    updates = dict(
        num_layers=depth,
        d_model=128,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=32 if heads else 0,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=vocab,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window
        else None,
        attn_chunk=64,
        remat="none",
    )
    if cfg.num_experts:
        # the router's 8 outputs, half of them held here
        updates.update(num_experts=min(cfg.num_experts, 8),
                       experts_held=min(cfg.num_experts, 8) // 2,
                       top_k=min(cfg.top_k, 2), expert_d_ff=64,
                       first_dense_ff=256 if cfg.first_dense_layers else 0)
    if cfg.mla:
        # a query-key width (16 + 8) unequal to the value width (16)
        updates.update(kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16)
    if cfg.family == "ssm":
        updates.update(ssm_state=16, ssm_head_dim=16, ssd_chunk=16)
    if cfg.lru_width:
        updates.update(lru_width=128)
    if cfg.enc_layers:
        updates.update(enc_layers=2)
    if cfg.frontend_tokens:
        updates.update(frontend_tokens=8)
    return dataclasses.replace(cfg, **updates)


__all__ = ["build_model", "get_model", "reduced_config", "list_archs"]
