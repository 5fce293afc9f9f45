"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward + one train step on CPU, asserting shapes + finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import list_archs
from repro.models import build_model, get_model, reduced_config
from repro.optim import AdamWConfig, apply_updates, init_state

KEY = jax.random.PRNGKey(0)


def make_inputs(cfg, B=2, S=32):
    toks = jax.random.randint(KEY, (B, S - cfg.frontend_tokens), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        batch = {"tokens": jax.random.randint(KEY, (B, S // 2), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(KEY, (B, S // 2), 0,
                                              cfg.vocab_size),
                 "frontend": jax.random.normal(KEY, (B, S // 2,
                                                     cfg.d_model))}
    elif cfg.frontend:
        batch["frontend"] = jax.random.normal(
            KEY, (B, cfg.frontend_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_forward_shapes_and_finite(arch):
    _, full = get_model(arch)
    cfg = reduced_config(full)
    model = build_model(cfg)
    params = model.init(KEY)
    B, S = 2, 32
    batch = make_inputs(cfg, B, S)
    if cfg.family == "encdec":
        logits, _ = model.forward(params, batch["frontend"],
                                  batch["tokens"])
        assert logits.shape == (B, S // 2, cfg.vocab_size)
    else:
        logits, _ = model.forward(params, batch["tokens"],
                                  batch.get("frontend"))
        exp = S if cfg.frontend else S
        assert logits.shape == (B, exp, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", list_archs())
def test_one_train_step(arch):
    _, full = get_model(arch)
    cfg = reduced_config(full)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = make_inputs(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt = init_state(params)

    @jax.jit
    def step(params, opt, batch):
        (loss, _), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        params, opt, metrics = apply_updates(opt_cfg, params, grads, opt)
        return params, opt, loss

    p1, o1, loss = step(params, opt, batch)
    assert bool(jnp.isfinite(loss))
    # params actually changed
    delta = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(p1)))
    assert delta > 0
    # no NaNs anywhere in the new state
    for leaf in jax.tree.leaves(p1):
        assert bool(jnp.isfinite(leaf).all())


@pytest.mark.parametrize("arch", list_archs())
def test_exact_config_matches_assignment(arch):
    """The full (non-reduced) configs carry the assigned hyperparameters."""
    _, cfg = get_model(arch)
    expected = {
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "phi3.5-moe-42b-a6.6b": (32, 4096, 32, 8, 6400, 32064),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[cfg.name]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected
    if cfg.name == "deepseek-moe-16b":
        assert (cfg.num_experts, cfg.top_k, cfg.num_shared_experts) == \
            (64, 6, 2)
    if cfg.name == "phi3.5-moe-42b-a6.6b":
        assert (cfg.num_experts, cfg.top_k) == (16, 2)
    if cfg.name == "mamba2-130m":
        assert cfg.ssm_state == 128
    if cfg.name == "deepseek-v2-lite":
        assert (cfg.num_experts, cfg.held_experts, cfg.top_k,
                cfg.num_shared_experts, cfg.expert_d_ff) == \
            (64, 64, 6, 2, 1408)
        assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert cfg.rope_scaling["factor"] == 40 and not cfg.norm_topk_prob
