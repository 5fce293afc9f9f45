"""The expert layer that holds a chip's share: routing, dropless grouped
compute, the balance losses, the counters, and the sum of the shares.

A plain form is written here for every check: each token's chosen
experts applied one by one and weighted by the router."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build_model, get_model, reduced_config
from repro.models.layers import init_from_specs, mlp_apply
from repro.models.moe import moe_apply, moe_specs, route

HI = "highest"


def _cfg(arch="deepseek-v2-lite", **kw):
    _, full = get_model(arch)
    return dataclasses.replace(reduced_config(full), dtype="float32", **kw)


def _params(cfg, key=0):
    return init_from_specs(jax.random.PRNGKey(key), moe_specs(cfg))


def _plain(params, x, cfg):
    """The held experts' part of the layer, token by token, plus the
    shared experts."""
    weights, experts, _ = route(params, x, cfg)
    held = params["w_gate"].shape[0]
    w, e = np.asarray(weights), np.asarray(experts)
    xs = np.asarray(x)
    y = np.zeros(xs.shape, np.float32)
    for b in range(xs.shape[0]):
        for t in range(xs.shape[1]):
            for j in range(cfg.top_k):
                i = e[b, t, j]
                if 0 <= i < held:
                    h = jax.nn.silu(xs[b, t] @ params["w_gate"][i]) \
                        * (xs[b, t] @ params["w_up"][i])
                    y[b, t] += w[b, t, j] * np.asarray(
                        h @ params["w_down"][i])
    if cfg.num_shared_experts:
        y = y + np.asarray(mlp_apply(params["shared"], x, cfg))
    return y


def _share(params, i, held):
    """Share ``i`` of the experts, presented as the layer's experts 0 ..
    held - 1: the router's columns rolled by ``i * held``, which routes
    every token alike and leaves the balance loss as it was."""
    out = dict(params, router=jnp.roll(params["router"], -i * held, axis=1))
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = params[k][i * held:(i + 1) * held]
    return out


@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    """With the router's 8 outputs over 8 // held shares, each holding
    ``held`` experts: the shares' outputs, the shared experts counted
    once, add up to the layer that holds all 8, and to the plain form."""
    cfg = _cfg(experts_held=0)
    full = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    shared = mlp_apply(full["shared"], x, cfg)
    n = cfg.num_experts // held
    with jax.default_matmul_precision(HI):
        whole, st = moe_apply(full, x, cfg)
        parts = [moe_apply(_share(full, i, held), x, cfg) for i in range(n)]
    total = sum(y for y, _ in parts) - (n - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    with jax.default_matmul_precision(HI):
        np.testing.assert_allclose(np.asarray(whole), _plain(full, x, cfg),
                                   rtol=1e-4, atol=1e-5)
    # the rolled router routes alike: the same balance loss in every share
    for _, s in parts:
        np.testing.assert_allclose(float(s["aux"]), float(st["aux"]),
                                   rtol=1e-6)
    # every choice lands on exactly one share
    assert sum(int(s["moe_held_rows"]) for _, s in parts) == \
        int(st["moe_held_rows"]) == 2 * 16 * cfg.top_k


def _forced(cfg, params, expert):
    """A router whose logit for ``expert`` dwarfs the others on inputs
    with positive entries: every token chooses it first."""
    r = params["router"] * 0.01
    return dict(params, router=r.at[:, expert].set(10.0))


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "phi3.5-moe-42b-a6.6b"])
def test_dropless_under_a_router_that_sends_every_token_to_one_expert(arch):
    cfg = _cfg(arch)
    held = cfg.held_experts
    params = _forced(cfg, _params(cfg, 5), expert=1)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                  (2, 32, cfg.d_model)))
    with jax.default_matmul_precision(HI):
        y, st = moe_apply(params, x, cfg)
        want = _plain(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    _, experts, _ = route(params, x, cfg)
    e = np.asarray(experts).reshape(-1)
    assert (np.asarray(experts)[..., 0] == 1).all()
    counts = np.bincount(e[e < held], minlength=held)
    assert counts[1] == 64                   # every token, none dropped
    assert int(st["moe_held_rows"]) == counts.sum()
    assert float(st["moe_load_max"]) == pytest.approx(
        64 * held / counts.sum())


def test_counters_by_hand():
    cfg = _cfg()
    params = _params(cfg, 7)
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 16, cfg.d_model))
    held = cfg.held_experts
    # experts 2 .. held + 1 of the router's, as the layer's 0 .. held - 1
    shifted = dict(params, router=jnp.roll(params["router"], -2, axis=1))
    _, st = moe_apply(shifted, x, cfg)
    _, experts, _ = route(params, x, cfg)
    e = (np.asarray(experts).reshape(-1) - 2) % cfg.num_experts
    counts = np.array([(e == i).sum() for i in range(held)])
    assert int(st["moe_held_rows"]) == counts.sum()
    assert float(st["moe_load_max"]) == pytest.approx(
        counts.max() / counts.mean())


def test_gate_and_balance_losses_by_hand():
    """DeepSeek-V2's gate: softmax scores, top-k not renormalised, the
    per-sequence loss alpha * mean_b sum_e (count_be / (S k / E)) *
    mean_s score_bse.  Switch's (the other MoE configurations): the
    renormalised top-k and coef * E * sum_e f_e P_e over the batch."""
    for arch, renorm in [("deepseek-v2-lite", False),
                         ("deepseek-moe-16b", True)]:
        cfg = _cfg(arch)
        params = _params(cfg, 9)
        x = jax.random.normal(jax.random.PRNGKey(10), (2, 8, cfg.d_model))
        w, e, aux = route(params, x, cfg)
        scores = np.asarray(jax.nn.softmax(
            np.asarray(x) @ np.asarray(params["router"]), -1))
        top = np.sort(scores, -1)[..., ::-1][..., :cfg.top_k]
        if renorm:
            top = top / top.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w), top, rtol=1e-5)
        ne, k = cfg.num_experts, cfg.top_k
        counts = np.stack([np.bincount(np.asarray(e)[b].reshape(-1),
                                       minlength=ne) for b in range(2)])
        if cfg.router_aux == "seq":
            want = cfg.router_aux_coef * np.mean(np.sum(
                counts / (8 * k / ne) * scores.mean(1), -1))
        else:
            want = cfg.router_aux_coef * ne * np.sum(
                counts.sum(0) / 16 * scores.mean((0, 1)))
        assert float(aux) == pytest.approx(want, rel=1e-5)


def test_trainer_metrics_carry_the_counters():
    from repro.launch.train import build
    trainer, _ = build("deepseek-v2-lite", reduced=True, seq_len=16,
                       global_batch=2, steps=2)
    trainer.cfg.log_period = 1
    trainer.train()
    m = trainer.metrics[-1]
    cfg = trainer.model.cfg
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    assert 0 < m["moe_held_rows"] <= moe_layers * 2 * 16 * cfg.top_k
    assert m["moe_held_rows"] == int(m["moe_held_rows"])
    assert 1.0 <= m["moe_load_max"] <= cfg.held_experts


def test_model_loss_counts_every_moe_layer():
    """The loss's counters: the rows summed over the MoE layers, the
    largest layer's load, each layer run alone on its own input."""
    from repro.models.moe import zero_stats
    from repro.models.transformer import block_apply
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    toks = jax.random.randint(jax.random.PRNGKey(12), (2, 16), 0,
                              cfg.vocab_size)
    _, parts = model.loss(params, {"tokens": toks, "labels": toks})
    x = params["embed"]["tokens"][toks]
    x, _ = block_apply(params["head0"], x, cfg, "moe", zero_stats(cfg))
    rows, worst = 0, 0.0
    for i in range(cfg.num_layers - cfg.first_dense_layers):
        layer = jax.tree.map(lambda a: a[i], params["blocks"]["p0"])
        x, one = block_apply(layer, x, cfg, "moe", zero_stats(cfg))
        rows += int(one["moe_held_rows"])
        worst = max(worst, float(one["moe_load_max"]))
    assert int(parts["counters"]["moe_held_rows"]) == rows
    assert float(parts["counters"]["moe_load_max"]) == pytest.approx(worst)


def test_parameter_count_of_the_published_model_and_of_one_chips_share():
    """DeepSeek-V2-Lite has 15.7B parameters; one chip's share of the
    benchmark's cut (5 layers, 8 of 64 experts, 12,800 vocabulary rows)
    has 52,428,800 in the embedding and head, 81,007,104 in the dense
    layer, 4 x 100,405,760 in the MoE layers and 2,048 in the final
    norm."""
    _, full = get_model("deepseek-v2-lite")
    assert full.param_count() == pytest.approx(15.7e9, rel=0.005)
    cut = dataclasses.replace(full, num_layers=5, experts_held=8,
                              vocab_size=12800)
    assert cut.param_count() == 535_060_992
    model = build_model(cut)
    leaves = jax.tree.leaves(jax.eval_shape(model.init,
                                            jax.random.PRNGKey(0)))
    assert sum(x.size for x in leaves) == 535_060_992


def _undefined_past_groups(real):
    """A grouped matmul like the TPU's: rows past the last group are left
    undefined (NaN here), in the output and in the gradient to the rows."""
    @jax.custom_vjp
    def ragged_dot(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        past = (jnp.arange(lhs.shape[0]) >= sizes.sum())[:, None]
        return jnp.where(past, jnp.nan, out).astype(out.dtype)

    def fwd(lhs, rhs, sizes):
        return ragged_dot(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        past = (jnp.arange(lhs.shape[0]) >= sizes.sum())[:, None]
        return (jnp.where(past, jnp.nan, d_lhs).astype(d_lhs.dtype), d_rhs,
                None)
    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


def test_rows_past_the_held_groups_do_not_leak(monkeypatch):
    """Where the grouped matmul leaves the rows of pairs held elsewhere
    undefined, the layer's output and every gradient stay those of a
    grouped matmul that zeroes them."""
    import repro.models.moe as moe_mod
    cfg = _cfg()
    params = _params(cfg, 13)
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 16, cfg.d_model))
    r = jax.random.normal(jax.random.PRNGKey(15), x.shape)

    def loss(p, x):
        return (moe_mod.moe_apply(p, x, cfg)[0] * r).sum()
    want = jax.grad(loss, argnums=(0, 1))(params, x)
    fake = _undefined_past_groups(jax.lax.ragged_dot)
    monkeypatch.setattr(moe_mod.jax.lax, "ragged_dot", fake)
    got = jax.grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
