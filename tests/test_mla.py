"""Multi-head latent attention and YaRN rope (DeepSeek-V2) against numbers
worked out by hand from the published formulas (HF ``modeling_deepseek``)
and against plain per-head forms; chunked attention at a value width that
differs from the query's; the fused kernel that runs MLA's core on a TPU,
in interpret mode, against the chunked core."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import get_model, reduced_config
from repro.models.attention import (chunked_attention,
                                    fused_causal_attention, mla_apply,
                                    mla_scale, mla_specs)
from repro.models.layers import (apply_rope, init_from_specs, rms_norm,
                                 rope_frequencies, rope_mscale)

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def test_yarn_frequencies_by_hand():
    """DeepSeek-V2-Lite's rotary part (64 wide, theta 10,000): the
    correction range of beta_fast 32 .. beta_slow 1 over the original
    4,096 positions is pairs 10 .. 23, since
    64 ln(4096 / (32 * 2 pi)) / (2 ln 10^4) = 10.47 (floor 10) and
    64 ln(4096 / (2 pi)) / (2 ln 10^4) = 22.51 (ceil 23)."""
    inv = np.asarray(rope_frequencies(64, 10000.0, YARN))
    assert inv.shape == (32,)
    assert inv[0] == pytest.approx(1.0)                     # unscaled
    assert inv[10] == pytest.approx(10 ** -1.25, rel=1e-6)  # ramp starts
    # pair 16: 6/13 of the way to the frequency divided by 40
    assert inv[16] == pytest.approx(0.01 * 7 / 13 + 0.01 / 40 * 6 / 13,
                                    rel=1e-6)
    assert inv[23] == pytest.approx(10 ** (-46 / 16) / 40, rel=1e-6)
    assert inv[31] == pytest.approx(10 ** (-62 / 16) / 40, rel=1e-6)
    plain = np.asarray(rope_frequencies(64, 10000.0))
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)


def test_yarn_mscale_and_softmax_scale_by_hand():
    # cos/sin: yarn_get_mscale(40, 0.707) / yarn_get_mscale(40, 0.707)
    assert rope_mscale(YARN) == 1.0
    # mscale 1 over mscale_all_dim 0: (0.1 ln 40 + 1) / 1
    assert rope_mscale(dict(YARN, mscale=1.0, mscale_all_dim=0.0)) == \
        pytest.approx(1.3688879454, rel=1e-9)
    assert rope_mscale(None) == 1.0
    # softmax: (0.0707 ln 40 + 1)^2 / sqrt(128 + 64) = 1.5896 / sqrt(192)
    _, cfg = get_model("deepseek-v2-lite")
    assert mla_scale(cfg) == pytest.approx(1.58962617 / math.sqrt(192),
                                           rel=1e-7)
    assert mla_scale(cfg) == pytest.approx(0.114721, rel=1e-5)
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert mla_scale(plain) == 1 / math.sqrt(192)


def _hf_rope(x, pos, inv, mscale):
    """HF DeepSeek-V2: de-interleave (view d/2, 2 and transpose), then
    rotate_half with cos and sin of cat(freqs, freqs)."""
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    ang = pos[:, :, None].astype(np.float32) * inv[None, None, :]
    emb = np.concatenate([ang, ang], -1)[:, :, None, :]
    rot = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * np.cos(emb) * mscale + rot * np.sin(emb) * mscale


@pytest.mark.parametrize("scaling", [YARN, dict(YARN, mscale=1.0,
                                                mscale_all_dim=0.0)])
def test_interleaved_rope_is_hf_deinterleave(scaling):
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16)))
    pos = np.broadcast_to(np.arange(9) * 37, (2, 9))
    got = apply_rope(jnp.asarray(x), jnp.asarray(pos), 10000.0, scaling,
                     interleaved=True)
    inv = np.asarray(rope_frequencies(16, 10000.0, scaling))
    want = _hf_rope(x, pos, inv, rope_mscale(scaling))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def _plain_attention(q, k, v, scale):
    """(B, S, H, D) q and k, (B, S, H, Dv) v: the full causal softmax."""
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_chunked_attention_value_width_and_scale(kv_heads):
    cfg = dataclasses.replace(reduced_config(get_model("smollm-135m")[1]),
                              attn_chunk=16, dtype="float32")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 24))
    k = jax.random.normal(ks[1], (2, 64, kv_heads, 24))
    v = jax.random.normal(ks[2], (2, 64, kv_heads, 16))
    with jax.default_matmul_precision("highest"):
        got = chunked_attention(q, k, v, cfg, causal=True, window=None,
                                scale=0.37)
        rep = 4 // kv_heads
        want = _plain_attention(q, jnp.repeat(k, rep, 2),
                                jnp.repeat(v, rep, 2), 0.37)
    assert got.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _mla_cfg():
    _, full = get_model("deepseek-v2-lite")
    return dataclasses.replace(reduced_config(full), dtype="float32",
                               attn_chunk=8)


def test_mla_forward_matches_plain_per_head_form():
    """Each head h: query [q_nope_h, rope(q_pe_h)], key [k_nope_h,
    rope(k_pe)] with k_pe shared, value v_h, from the normalised latent."""
    cfg = _mla_cfg()
    p = init_from_specs(jax.random.PRNGKey(2), mla_specs(cfg))
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                           p["kv_norm"].shape)
    b, s = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.d_model))
    nope, rp, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    with jax.default_matmul_precision("highest"):
        got = mla_apply(p, x, cfg)
        ckv = x @ p["wkv_a"]
        c = rms_norm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(ckv[..., None, r:], pos, cfg.rope_theta,
                          cfg.rope_scaling, interleaved=True)[:, :, 0]
        want = jnp.zeros((b, s, cfg.d_model))
        for h in range(cfg.num_heads):
            q = x @ p["wq"][:, h]
            q_pe = apply_rope(q[..., None, nope:], pos, cfg.rope_theta,
                              cfg.rope_scaling, interleaved=True)[:, :, 0]
            kv = c @ p["wkv_b"][:, h]
            qh = jnp.concatenate([q[..., :nope], q_pe], -1)
            kh = jnp.concatenate([kv[..., :nope], k_pe], -1)
            o = _plain_attention(qh[:, :, None], kh[:, :, None],
                                 kv[:, :, None, nope:], mla_scale(cfg))
            want = want + o[:, :, 0] @ p["wo"][h]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "smollm-135m"])
def test_nested_remat_keeps_loss_and_gradients(arch):
    """Rematerialising each layer's attention again inside the layer's
    backward pass (``remat="nested"``, DeepSeek-V2-Lite's) computes what
    no rematerialisation and the layer's alone do: the same loss,
    readings and gradients."""
    from repro.models import build_model
    _, full = get_model(arch)
    base = dataclasses.replace(reduced_config(full), dtype="float32")
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                              base.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    params = build_model(base).init(jax.random.PRNGKey(6))
    got = {}
    for remat in ("none", "nothing_saveable", "nested"):
        model = build_model(dataclasses.replace(base, remat=remat))
        with jax.default_matmul_precision("highest"):
            got[remat] = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch)
    (loss, parts), grads = got["none"]
    for remat in ("nothing_saveable", "nested"):
        (l2, p2), g2 = got[remat]
        np.testing.assert_allclose(float(l2), float(loss), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(parts)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)


# -- the fused core (JAX's splash-attention kernel, in interpret mode) -------


def _core_inputs(b, s, h, d, dv, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, d)),
            jax.random.normal(ks[1], (b, s, h, d)),
            jax.random.normal(ks[2], (b, s, h, dv)),
            jax.random.normal(ks[3], (b, s, h, dv)))


def _gap(got, want):
    """Largest gap over the largest magnitude of the reference."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("d,dv,s,h", [
    (24, 16, 256, 4),            # the reduced configuration's widths
    (192, 128, 512, 2),          # DeepSeek-V2-Lite's
])
def test_fused_core_matches_chunked(block, d, dv, s, h):
    """Causal, at MLA's scale: the forward and the gradients to q, k and v
    of the fused kernel match the chunked core's."""
    cfg = dataclasses.replace(_mla_cfg(), attn_chunk=64)
    scale = mla_scale(get_model("deepseek-v2-lite")[1])
    q, k, v, ct = _core_inputs(2, s, h, d, dv)

    def fused(q, k, v):
        return fused_causal_attention(q, k, v, scale, block=block,
                                      interpret=True)

    def chunked(q, k, v):
        return chunked_attention(q, k, v, cfg, causal=True, window=None,
                                 scale=scale)

    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(fused)(q, k, v), jax.jit(chunked)(q, k, v)
        grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * ct),
                                  (0, 1, 2)))(q, k, v)
                 for f in (fused, chunked)]
    assert got.shape == (2, s, h, dv)
    assert _gap(got, want) < 2e-6
    for g, w in zip(*grads):
        assert _gap(g, w) < 2e-5


def test_mla_layer_on_the_kernel_path_under_nested_remat(monkeypatch):
    """The reduced DeepSeek-V2-Lite model under ``remat="nested"`` (its
    MLA forward three times a step): the kernel path gives the loss and the
    gradients the chunked path does.  Every layer's MLP is dense here: the
    routed experts' top-k turns a rounding-sized change of a layer's output
    into a change of the experts a token meets."""
    from repro.models import attention, build_model
    cfg = _mla_cfg()
    cfg = dataclasses.replace(cfg, remat="nested", attn_chunk=64,
                              first_dense_layers=cfg.num_layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(8))
    toks = jax.random.randint(jax.random.PRNGKey(9), (2, 256), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}

    def loss_and_grads():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(model.loss, has_aux=True))(
                params, batch)
    (loss, _), grads = loss_and_grads()
    real = attention.fused_causal_attention
    calls = []

    def fused(*args, **kw):
        calls.append(kw.get("mesh"))
        return real(*args, **dict(kw, block=128, interpret=True))
    monkeypatch.setattr(attention, "_traced_platform", lambda mesh: "tpu")
    monkeypatch.setattr(attention, "fused_causal_attention", fused)
    (k_loss, _), k_grads = loss_and_grads()
    assert calls and calls[0] is None
    np.testing.assert_allclose(float(k_loss), float(loss), rtol=1e-6)
    for g, w in zip(jax.tree.leaves(k_grads), jax.tree.leaves(grads)):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)


def test_fused_core_per_batch_shard_on_four_devices():
    """On a mesh of 4 devices the kernel runs per shard of the batch
    (``shard_map``): the forward and the gradients match the chunked core
    on one device."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import make_mesh
        from repro.models import get_model, reduced_config
        from repro.models.attention import (chunked_attention,
                                            fused_causal_attention)
        cfg = dataclasses.replace(
            reduced_config(get_model("deepseek-v2-lite")[1]), attn_chunk=64)
        mesh = make_mesh(4, 1)
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        shapes = [(4, 256, 2, 24)] * 2 + [(4, 256, 2, 16)] * 2
        q, k, v, ct = [jax.device_put(jax.random.normal(kk, sh),
                                      NamedSharding(mesh, P("data")))
                       for kk, sh in zip(ks, shapes)]
        fused = lambda q, k, v: fused_causal_attention(
            q, k, v, 0.3, mesh=mesh, block=128, interpret=True)
        chunked = lambda q, k, v: chunked_attention(
            q, k, v, cfg, causal=True, window=None, scale=0.3)
        gap = lambda a, b: float(jnp.max(jnp.abs(a - b))
                                 / jnp.max(jnp.abs(b)))
        out = {}
        with jax.default_matmul_precision("highest"):
            lowered = jax.jit(fused).lower(q, k, v).as_text()
            got = jax.jit(fused)(q, k, v)
            want = jax.jit(chunked)(q, k, v)
            grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * ct),
                                      (0, 1, 2)))(q, k, v)
                     for f in (fused, chunked)]
        out["shard_map"] = "sdy.manual_computation" in lowered \\
            or "shard_map" in lowered
        out["out_sharding"] = str(got.sharding.spec)
        out["fwd"] = gap(got, want)
        out["grads"] = [gap(g, w) for g, w in zip(*grads)]
        print(json.dumps(out))
        """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["shard_map"], out
    assert "data" in out["out_sharding"]
    assert out["fwd"] < 2e-6
    assert max(out["grads"]) < 2e-5
