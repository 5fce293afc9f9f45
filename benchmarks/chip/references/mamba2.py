"""Plain float32 reference of the Mamba-2 language model (Mamba2-130M).

Written from the paper (arXiv:2405.21060, section 7 and Listing 1) and the
published ``mamba_ssm`` model: token embedding; per layer a pre-norm
Mamba-2 mixer added to the residual stream; a final RMSNorm; logits
through the tied embedding, whose ``vocab_rows`` rows are ``vocab_size``
padded to a multiple of ``pad_vocab_size_multiple`` as ``mamba_ssm`` pads
them; mean token cross-entropy.  The mixer: one input
projection to (z, x, B, C, dt); a causal depthwise convolution of width
``d_conv`` with bias and SiLU over (x, B, C); dt = softplus(dt + dt_bias);
the selective state-space map with one group (B and C shared by the
heads) and a skip D per head; a gated RMSNorm, rmsnorm(y * silu(z)); the
output projection.

The state-space map is computed in its quadratic ("dual") form, straight
from the definition and not by chunks:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < k <= t} dt_k A) dt_s x_s + D x_t

so it shares nothing with the chunked scan of the program under test.
Every array is float32 and every matmul runs at ``highest`` precision;
layers are stacked and scanned, each under ``jax.checkpoint``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    return {"d": d, "layers": cfg["n_layer"], "di": di,
            "n": cfg["d_state"], "p": cfg["headdim"],
            "heads": di // cfg["headdim"], "k": cfg["d_conv"],
            "vocab": cfg["vocab_rows"], "eps": cfg["norm_epsilon"],
            "std": cfg["initializer_range"]}


NORMS = ("layers.norm", "layers.gate_norm", "final_norm")


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, L, di, n, h = s["d"], s["layers"], s["di"], s["n"], s["heads"]
    conv = di + 2 * n
    return {
        "embed": (s["vocab"], d),
        "final_norm": (d,),
        "layers": {
            "norm": (L, d),
            "in_proj": (L, d, 2 * di + 2 * n + h),
            "conv_w": (L, s["k"], conv),
            "conv_b": (L, conv),
            "dt_bias": (L, h),
            "A_log": (L, h),
            "D": (L, h),
            "gate_norm": (L, di),
            "out_proj": (L, di, d),
        },
    }


def init_params(key, cfg: dict) -> dict:
    """``mamba_ssm``'s init where it matters to the numbers: A = -[1, 16]
    uniform, dt_bias the inverse softplus of dt log-uniform in [0.001, 0.1],
    D = 1, norm weights 1, conv weights uniform in +-1/sqrt(d_conv), conv
    bias 0; normal(0, initializer_range) for the projections and the
    embedding."""
    s = sizes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = ".".join(p.key for p in path)
        k = jax.random.fold_in(key, i)
        if name in NORMS or name == "layers.D":
            v = jnp.ones(shape, F32)
        elif name == "layers.conv_b":
            v = jnp.zeros(shape, F32)
        elif name == "layers.A_log":
            v = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
        elif name == "layers.dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, F32, jnp.log(1e-3), jnp.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif name == "layers.conv_w":
            bound = 1.0 / s["k"] ** 0.5
            v = jax.random.uniform(k, shape, F32, -bound, bound)
        else:
            v = s["std"] * jax.random.normal(k, shape, F32)
        out.append(v)
    return jax.tree_util.tree_unflatten(tree, out)


def to_program(p: dict, cfg: dict) -> dict:
    ly = p["layers"]
    return {
        "embed": {"tokens": p["embed"]},
        "blocks": {"p0": {
            "ln1": ly["norm"] - 1.0,
            "mixer": {"in_proj": ly["in_proj"], "conv_w": ly["conv_w"],
                      "conv_b": ly["conv_b"], "A_log": ly["A_log"],
                      "D": ly["D"], "dt_bias": ly["dt_bias"],
                      "norm": ly["gate_norm"] - 1.0,
                      "out_proj": ly["out_proj"]},
        }},
        "final_norm": p["final_norm"] - 1.0,
    }


def from_program(t: dict, cfg: dict) -> dict:
    """A program-layout tree of gradients, moments or parameter changes in
    this layout (linear: no offset is added back)."""
    b = t["blocks"]["p0"]
    m = b["mixer"]
    return {
        "embed": t["embed"]["tokens"],
        "final_norm": t["final_norm"],
        "layers": {"norm": b["ln1"], "in_proj": m["in_proj"],
                   "conv_w": m["conv_w"], "conv_b": m["conv_b"],
                   "dt_bias": m["dt_bias"], "A_log": m["A_log"],
                   "D": m["D"], "gate_norm": m["norm"],
                   "out_proj": m["out_proj"]},
    }


# -- the model ----------------------------------------------------------------


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def segsum(a):
    """a: (..., T) -> (..., T, T) with [t, s] = sum_{s < k <= t} a_k for
    s <= t and -inf above the diagonal (summed directly, not as a
    difference of two long cumulative sums)."""
    t = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (t,))   # [k, s] = a_k
    below = jnp.tril(jnp.ones((t, t), bool), -1)               # k > s
    sums = jnp.cumsum(jnp.where(below, rep, 0.0), axis=-2)     # over k <= t
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), sums, -jnp.inf)


def causal_conv(x, w, b):
    """x: (B, S, C); w: (K, C); out_t = b + sum_j w_j x_{t-K+1+j}."""
    k, seq = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[j] * xp[:, j:j + seq] for j in range(k))


def _layer(s):
    di, n, h, p = s["di"], s["n"], s["heads"], s["p"]

    def layer(x, w):
        bsz, seq, _ = x.shape
        u = rms_norm(x, w["norm"], s["eps"])
        proj = u @ w["in_proj"]
        z = proj[..., :di]
        xbc = proj[..., di:2 * di + 2 * n]
        dt = proj[..., 2 * di + 2 * n:]
        xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
        xs = xbc[..., :di].reshape(bsz, seq, h, p)
        bm = xbc[..., di:di + n]
        cm = xbc[..., di + n:]
        dt = jax.nn.softplus(dt + w["dt_bias"])                 # (B,S,H)
        a = dt * -jnp.exp(w["A_log"])                           # (B,S,H)
        decay = jnp.exp(segsum(jnp.moveaxis(a, -1, 1)))         # (B,H,T,S)
        cb = jnp.einsum("btn,bsn->bts", cm, bm)
        y = jnp.einsum("bts,bhts,bshp->bthp", cb, decay,
                       xs * dt[..., None])
        y = y + xs * w["D"][None, None, :, None]
        y = y.reshape(bsz, seq, di)
        y = rms_norm(y * jax.nn.silu(z), w["gate_norm"], s["eps"])
        return x + y @ w["out_proj"], None
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

    6 per weight of the input and output projections and of the tied
    output head, 6 per depthwise convolution weight, plus the state-space
    map in the chunked form the configuration runs (chunk Q, state N, one
    group): within a chunk the causal C.B scores (Q+1)/2 keys of width N
    and the causal mixing of (Q+1)/2 values of width heads*headdim; across
    chunks the output from the carried state and the state update, each
    N*heads*headdim per token.  2 FLOPs a multiply-add, 3 times the forward.
    """
    s = sizes(cfg)
    d, di, n, h = s["d"], s["di"], s["n"], s["heads"]
    q = min(cfg["chunk_size"], seq_len)
    proj_w = d * (2 * di + 2 * n + h) + di * d
    conv_w = s["k"] * (di + 2 * n)
    matmul_w = s["layers"] * (proj_w + conv_w) + s["vocab"] * d
    ssd = (q + 1) / 2 * n + (q + 1) / 2 * di + 2 * n * di
    return 6.0 * matmul_w + s["layers"] * 3 * 2 * ssd
