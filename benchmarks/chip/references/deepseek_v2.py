"""Plain float32 reference of DeepSeek-V2 (DeepSeek-V2-Lite): one chip's
share of each layer's experts.

Written from the published description (HF ``modeling_deepseek.py``):
token embedding; per layer a pre-norm multi-head latent attention
(``DeepseekV2Attention``) and a pre-norm feed-forward, both added to the
residual stream; the first ``first_k_dense_replace`` layers' feed-forward
is a dense SiLU-gated MLP, every later one a mixture of experts
(``DeepseekV2MoE``); a final RMSNorm; logits through an untied head; mean
token cross-entropy.

- Attention (no query latent, ``q_lora_rank`` null): ``q_proj`` gives each
  head a plain part (``qk_nope_head_dim``) and a rotary part
  (``qk_rope_head_dim``); ``kv_a_proj_with_mqa`` gives a ``kv_lora_rank``
  latent, normalised by ``kv_a_layernorm``, and one rotary key shared by
  the heads; ``kv_b_proj`` maps the latent to each head's key part and
  value (``v_head_dim``).  Rope is YaRN (``DeepseekV2YarnRotaryEmbedding``):
  frequencies ramped between the plain and the ``factor``-stretched ones
  over the correction range of ``beta_fast``..``beta_slow``, cos and sin
  times mscale / mscale_all_dim, and ``apply_rotary_pos_emb``'s
  de-interleave of the rotary dimensions before ``rotate_half``.  The
  softmax scale is 1/sqrt(qk width) times yarn_get_mscale(factor,
  mscale_all_dim) squared.
- Experts (``MoEGate``, ``DeepseekV2MoE`` in training): float32 router
  logits over all ``router_experts`` (the published ``n_routed_experts``),
  softmax, greedy top-k, renormalised only if ``norm_topk_prob``, times
  ``routed_scaling_factor``; the shared experts as one MLP of width
  ``n_shared_experts * moe_intermediate_size``; the per-sequence balance
  loss (``seq_aux``) with ``aux_loss_alpha``.

Departures, each noted:

- The chip's share: the layer holds experts 0 .. ``n_routed_experts`` - 1
  of ``router_experts`` (the benchmark's cut: 8 chips share each layer).
  Each held expert is applied to every token, one expert at a time, and
  its output weighted by the token's gate weight for it, 0 where the
  token did not choose it; an expert held elsewhere adds nothing, as in
  the program.
- The balance loss is in the loss's value; HF adds its gradient alone
  (``AddAuxiliaryLoss``).  The gradient is the same.
- Random weights: normal(0, ``initializer_range``) for every matrix, the
  router included (HF's ``MoEGate`` starts from kaiming-uniform, bound
  1/sqrt(hidden_size) = 0.022 at 2,048), ones for the norm weights.
- Attention runs in query blocks, one at a time (``jax.lax.map``) and
  each under ``jax.checkpoint``, and the layers each under
  ``jax.checkpoint``, and so do the held experts and blocks of the
  output head's tokens, so that one row of 8,192 tokens fits beside five
  copies of the parameters.

No kernels, no cache, no mixed precision: every array is float32 and every
matmul runs at ``highest`` precision (``references/train.py`` sets it).
Sizes come from the benchmark's configuration file (HF key names), never
from the program.  ``to_program`` and ``from_program`` map between this
layout and the program's parameter tree; they move values and do no
arithmetic except the norm weights, which the program holds as offsets
from 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512
HEAD_BLOCK = 2048


def sizes(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"],
            "heads": cfg["num_attention_heads"], "r": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "experts": cfg["router_experts"], "held": cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
            "std": cfg["initializer_range"]}


# -- parameters ---------------------------------------------------------------

NORMS = ("dense.attn_norm", "dense.kv_norm", "dense.mlp_norm",
         "layers.attn_norm", "layers.kv_norm", "layers.mlp_norm",
         "final_norm")


def _attn_shapes(s: dict, lead: tuple) -> dict:
    d, h, r = s["d"], s["heads"], s["r"]
    qk = s["nope"] + s["rope"]
    return {"attn_norm": lead + (d,), "wq": lead + (d, h * qk),
            "wkv_a": lead + (d, r + s["rope"]), "kv_norm": lead + (r,),
            "wkv_b": lead + (r, h * (s["nope"] + s["v"])),
            "wo": lead + (h * s["v"], d), "mlp_norm": lead + (d,)}


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, L = s["d"], s["layers"] - s["dense"]
    if s["dense"] != 1:
        raise ValueError("the reference holds one leading dense layer")
    dense = _attn_shapes(s, ())
    dense.update(w_gate=(d, s["ff"]), w_up=(d, s["ff"]), w_down=(s["ff"], d))
    layers = _attn_shapes(s, (L,))
    layers.update(router=(L, d, s["experts"]),
                  w_gate=(L, s["held"], d, s["f"]),
                  w_up=(L, s["held"], d, s["f"]),
                  w_down=(L, s["held"], s["f"], d),
                  s_gate=(L, d, s["shared"]), s_up=(L, d, s["shared"]),
                  s_down=(L, s["shared"], d))
    return {"embed": (s["vocab"], d), "lm_head": (d, s["vocab"]),
            "final_norm": (d,), "dense": dense, "layers": layers}


def init_params(key, cfg: dict) -> dict:
    """normal(0, initializer_range) for every matrix and the embedding,
    ones for the norm weights."""
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


def _attn_to_program(w: dict, s: dict, lead: tuple) -> dict:
    d, h, r = s["d"], s["heads"], s["r"]
    return {"wq": w["wq"].reshape(lead + (d, h, s["nope"] + s["rope"])),
            "wkv_a": w["wkv_a"], "kv_norm": w["kv_norm"] - 1.0,
            "wkv_b": w["wkv_b"].reshape(lead + (r, h, s["nope"] + s["v"])),
            "wo": w["wo"].reshape(lead + (h, s["v"], d))}


def to_program(p: dict, cfg: dict) -> dict:
    s = sizes(cfg)
    L = s["layers"] - s["dense"]
    dn, ly = p["dense"], p["layers"]
    return {
        "embed": {"tokens": p["embed"], "unembed": p["lm_head"]},
        "head0": {"ln1": dn["attn_norm"] - 1.0,
                  "attn": _attn_to_program(dn, s, ()),
                  "ln2": dn["mlp_norm"] - 1.0,
                  "ffn": {"w_gate": dn["w_gate"], "w_up": dn["w_up"],
                          "w_down": dn["w_down"]}},
        "blocks": {"p0": {
            "ln1": ly["attn_norm"] - 1.0,
            "attn": _attn_to_program(ly, s, (L,)),
            "ln2": ly["mlp_norm"] - 1.0,
            "ffn": {"router": ly["router"], "w_gate": ly["w_gate"],
                    "w_up": ly["w_up"], "w_down": ly["w_down"],
                    "shared": {"w_gate": ly["s_gate"], "w_up": ly["s_up"],
                               "w_down": ly["s_down"]}},
        }},
        "final_norm": p["final_norm"] - 1.0,
    }


def _attn_from_program(a: dict, ln1, ln2, s: dict, lead: tuple) -> dict:
    d, h, r = s["d"], s["heads"], s["r"]
    return {"attn_norm": ln1,
            "wq": a["wq"].reshape(lead + (d, h * (s["nope"] + s["rope"]))),
            "wkv_a": a["wkv_a"], "kv_norm": a["kv_norm"],
            "wkv_b": a["wkv_b"].reshape(lead + (r, h * (s["nope"] + s["v"]))),
            "wo": a["wo"].reshape(lead + (h * s["v"], d)), "mlp_norm": ln2}


def from_program(t: dict, cfg: dict) -> dict:
    """A program-layout tree of gradients, moments or parameter changes in
    this layout (linear: no offset is added back)."""
    s = sizes(cfg)
    L = s["layers"] - s["dense"]
    h0, b = t["head0"], t["blocks"]["p0"]
    dense = _attn_from_program(h0["attn"], h0["ln1"], h0["ln2"], s, ())
    dense.update(w_gate=h0["ffn"]["w_gate"], w_up=h0["ffn"]["w_up"],
                 w_down=h0["ffn"]["w_down"])
    layers = _attn_from_program(b["attn"], b["ln1"], b["ln2"], s, (L,))
    f = b["ffn"]
    layers.update(router=f["router"], w_gate=f["w_gate"], w_up=f["w_up"],
                  w_down=f["w_down"], s_gate=f["shared"]["w_gate"],
                  s_up=f["shared"]["w_up"], s_down=f["shared"]["w_down"])
    return {"embed": t["embed"]["tokens"], "lm_head": t["embed"]["unembed"],
            "final_norm": t["final_norm"], "dense": dense, "layers": layers}


# -- the model ----------------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    ys = cfg["rope_scaling"]
    orig = ys["original_max_position_embeddings"]

    def correction_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    pos = jnp.arange(0, dim, 2, dtype=F32) / dim
    freq_extra = 1.0 / (base ** pos)
    freq_inter = 1.0 / (ys["factor"] * base ** pos)
    return freq_inter * (1 - mask) + freq_extra * mask


def rope(x, cfg: dict):
    """x: (B, S, heads, rope): HF's de-interleave, then ``rotate_half``
    with cos and sin times mscale / mscale_all_dim."""
    ys = cfg["rope_scaling"]
    m = yarn_get_mscale(ys["factor"], ys.get("mscale", 1)) \
        / yarn_get_mscale(ys["factor"], ys.get("mscale_all_dim", 0))
    b, seq, h, d = x.shape
    x = x.reshape(b, seq, h, d // 2, 2).swapaxes(-1, -2).reshape(b, seq, h, d)
    ang = jnp.arange(seq, dtype=F32)[:, None] * yarn_inv_freq(cfg)[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    return x * (jnp.cos(emb) * m) + rotate_half(x) * (jnp.sin(emb) * m)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    ys = cfg["rope_scaling"]
    if ys.get("mscale_all_dim"):
        m = yarn_get_mscale(ys["factor"], ys["mscale_all_dim"])
        scale = scale * m * m
    return scale


def attention(h, w, s: dict, cfg: dict):
    b, seq, _ = h.shape
    H, nope, rp = s["heads"], s["nope"], s["rope"]
    q = (h @ w["wq"]).reshape(b, seq, H, nope + rp)
    ckv = h @ w["wkv_a"]
    c = rms_norm(ckv[..., :s["r"]], w["kv_norm"], s["eps"])
    kv = (c @ w["wkv_b"]).reshape(b, seq, H, nope + s["v"])
    q_pe = rope(q[..., nope:], cfg)
    k_pe = rope(ckv[..., None, s["r"]:], cfg)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, seq, H, rp))], axis=-1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)

    @jax.checkpoint
    def block(args):
        q_blk, start = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * scale
        qpos = start + jnp.arange(q_blk.shape[1])
        causal = qpos[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    # one query block at a time, forward and backward
    n = min(Q_BLOCK, seq)
    blocks = q.reshape(b, seq // n, n, H, nope + rp).swapaxes(0, 1)
    o = jax.lax.map(block, (blocks, jnp.arange(0, seq, n)))
    o = o.swapaxes(0, 1).reshape(b, seq, H * s["v"])
    return o @ w["wo"]


def mlp(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def experts(h, w, s: dict, cfg: dict):
    """The held experts' share of the routed output, the shared experts,
    and the row's balance loss.  h: (B, S, d)."""
    scores = jax.nn.softmax(h @ w["router"], axis=-1)        # (B, S, E)
    weight, idx = jax.lax.top_k(scores, s["k"])              # greedy
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    weight = weight * cfg["routed_scaling_factor"]
    held = jnp.arange(s["held"])
    gate = jnp.sum(jnp.where(idx[..., None] == held, weight[..., None], 0.0),
                   axis=-2)                                  # (B, S, held)

    @jax.checkpoint
    def one(args):         # one held expert on every token, gate-weighted
        wg, wu, wd, g = args
        return mlp(h, wg, wu, wd) * g[..., None]
    routed = jax.lax.map(one, (w["w_gate"], w["w_up"], w["w_down"],
                               jnp.moveaxis(gate, -1, 0))).sum(axis=0)
    shared = mlp(h, w["s_gate"], w["s_up"], w["s_down"])
    # seq_aux: per row, each expert's count of choices over S * k / E
    # times its mean score, summed over the experts
    seq = h.shape[1]
    counts = jnp.sum(idx[..., None] == jnp.arange(s["experts"]),
                     axis=(1, 2)).astype(F32)                # (B, E)
    ce = counts / (seq * s["k"] / s["experts"])
    aux = cfg["aux_loss_alpha"] * jnp.sum(ce * scores.mean(axis=1), axis=-1)
    return routed + shared, aux


def _layer(s: dict, cfg: dict, moe: bool):
    def layer(carry, w):
        x, aux = carry
        h = rms_norm(x, w["attn_norm"], s["eps"])
        x = x + attention(h, w, s, cfg)
        h = rms_norm(x, w["mlp_norm"], s["eps"])
        if moe:
            y, a = experts(h, w, s, cfg)
            aux = aux + a
        else:
            y = mlp(h, w["w_gate"], w["w_up"], w["w_down"])
        return (x + y, aux), None
    return jax.checkpoint(layer)


def token_nll_sum(p: dict, tokens, labels, cfg: dict):
    """Sum over the block's tokens of -log p(label), plus each row's
    balance loss times the row's length (so that the mean over tokens
    adds the mean over rows of the balance loss)."""
    s = sizes(cfg)
    x = p["embed"][tokens]
    carry = (x, jnp.zeros(tokens.shape[:1], F32))
    carry, _ = _layer(s, cfg, moe=False)(carry, p["dense"])
    (x, aux), _ = jax.lax.scan(_layer(s, cfg, moe=True), carry, p["layers"])
    x = rms_norm(x, p["final_norm"], s["eps"])

    @jax.checkpoint
    def nll(args):         # one block of tokens' logits at a time
        xb, lb = args
        logp = jax.nn.log_softmax(xb @ p["lm_head"], axis=-1)
        return -jnp.take_along_axis(logp, lb[..., None], axis=-1).sum()
    b, seq = tokens.shape
    n = min(HEAD_BLOCK, seq)
    xs = x.reshape(b, seq // n, n, s["d"]).swapaxes(0, 1)
    ls = labels.reshape(b, seq // n, n).swapaxes(0, 1)
    return jax.lax.map(nll, (xs, ls)).sum() + seq * aux.sum()


# -- work ---------------------------------------------------------------------


def routed_flops_per_token(cfg: dict) -> float:
    """Forward and backward FLOPs a token needs in the held experts: 6 per
    weight of the three expert matrices, times the held experts a token
    chooses on average (``num_experts_per_tok`` * held / router width),
    over the MoE layers."""
    s = sizes(cfg)
    per_token = s["k"] * s["held"] / s["experts"]
    return (s["layers"] - s["dense"]) * 6.0 * per_token * 3 * s["d"] * s["f"]


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a token needs, recomputation not counted.

    6 per weight of every matmul a token passes: MLA's four projections,
    the dense MLP, the router, the shared experts, the held experts it
    chooses on average (``routed_flops_per_token``) and the output head
    (the embedding lookup is a gather); plus causal attention: a query at
    position t scores t+1 keys over the query-key width and mixes t+1
    values over the value width, 2 FLOPs a multiply-add, per head,
    averaged over the sequence, and three times that for forward plus
    backward.
    """
    s = sizes(cfg)
    d, h = s["d"], s["heads"]
    qk = s["nope"] + s["rope"]
    mla_w = (d * h * qk + d * (s["r"] + s["rope"])
             + s["r"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)
    moe_layers = s["layers"] - s["dense"]
    matmul_w = (s["layers"] * mla_w + s["dense"] * 3 * d * s["ff"]
                + moe_layers * (d * s["experts"] + 3 * d * s["shared"])
                + d * s["vocab"])
    attn = s["layers"] * 3 * 2 * h * (qk + s["v"]) * (seq_len + 1) / 2
    return 6.0 * matmul_w + attn + routed_flops_per_token(cfg)
