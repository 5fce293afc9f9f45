"""Loop-unit programs for roofline extrapolation.

XLA's ``cost_analysis`` counts a while-loop body once, so a scanned-over-
layers model under-reports FLOPs/bytes/collective-bytes by ~the trip count.
For every cell we therefore also compile its *loop unit* — one pattern
repetition of the layer scan, with the exact remat policy the real program
uses — and correct:  ``total = full + (trips - 1) * unit``.

Train cells get two unit variants:
- ``flops`` unit: grad wrt (params, x) — correct FLOPs/bytes including
  weight gradients;
- ``coll`` unit: grad wrt x only — correct *per-iteration* collective bytes
  (TP forward psums + dgrad psums).  The data-parallel reduction of weight
  gradients happens once on the stacked tensors outside the loop and is
  already fully counted in the main HLO; the grad-wrt-x unit deliberately
  omits it.

Inner loops (attention kv chunks, SSD chunks) are python-unrolled in the
model code, so within a unit everything is counted exactly.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.models import encdec as ed
from repro.models import transformer as tf
from repro.models.moe import zero_stats as moe_zero_stats
from repro.core.sharding import activation_rules
from repro.models.layers import abstract_params, is_spec, logical_tree


def _wrap_act(fn, mesh, rules):
    def wrapped(*args):
        with activation_rules(mesh, rules):
            return fn(*args)
    return wrapped


def _shapes_of(specs):
    return jax.tree.map(lambda s: s.shape, specs, is_leaf=is_spec)


def _sh_tree(logical, shapes, mesh, rules):
    return jax.tree.map(
        lambda lg, sh: NamedSharding(mesh, rules.spec_for(lg, sh, mesh)),
        logical, shapes, is_leaf=lambda x: isinstance(x, tuple))


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.nothing_saveable
              if cfg.remat == "nothing_saveable"
              else jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn, policy=policy, prevent_cse=False)


def _cache_abs_of(cfg):
    def abs_of(spec):
        last = spec.logical[-1] if spec.logical else ""
        if last == "kv_seq":
            return jax.ShapeDtypeStruct(spec.shape, jnp.int32)
        if last == "state":
            return jax.ShapeDtypeStruct(spec.shape, jnp.float32)
        return jax.ShapeDtypeStruct(spec.shape, jnp.dtype(cfg.dtype))
    return abs_of


def lm_loops(model, cfg, shape, mesh, rules, kind: str, accum: int = 1):
    """LoopSpecs for a CausalLM cell (kind: train|prefill|decode).

    With grad accumulation the layer unit processes one *microbatch*.
    """
    from repro.launch.cells import LoopSpec   # local: avoid import cycle
    reps, _tail = model._pattern_layout()
    if reps <= 1:
        return ()
    unit_specs = {f"p{j}": tf.block_specs(cfg, kj)
                  for j, kj in enumerate(cfg.pattern)}
    up_abs = abstract_params(unit_specs, jnp.dtype(cfg.param_dtype))
    up_sh = _sh_tree(logical_tree(unit_specs), _shapes_of(unit_specs),
                     mesh, rules)
    b = shape.global_batch // (accum if kind == "train" else 1)
    if kind == "decode":
        s_tot = 1
    else:
        s_tot = shape.seq_len
    x_abs = jax.ShapeDtypeStruct((b, s_tot, cfg.d_model),
                                 jnp.dtype(cfg.dtype))
    x_sh = NamedSharding(mesh, rules.spec_for(("batch", "seq", "embed"),
                                              x_abs.shape, mesh))

    if kind == "train":
        unit = model.scan_unit()

        def fwd_ck(up, x):
            (x, stats), _ = unit((x, moe_zero_stats(cfg)), up)
            return x.astype(jnp.float32).sum() + stats["aux"]

        def unit_flops(up, x):
            return jax.grad(fwd_ck, argnums=(0, 1))(up, x)

        def unit_coll(up, x):
            return jax.grad(fwd_ck, argnums=1)(up, x)

        return (
            LoopSpec("unit_flops", unit_flops, (up_abs, x_abs),
                     (up_sh, x_sh), reps, ("flops",)),
            LoopSpec("unit_coll", unit_coll, (up_abs, x_abs),
                     (up_sh, x_sh), reps, ("coll",)),
        )

    if kind == "prefill":
        max_len = shape.seq_len

        def unit(up, x):
            caches = {}
            for j, kj in enumerate(cfg.pattern):
                x, caches[f"p{j}"] = tf.block_prefill(
                    up[f"p{j}"], x, cfg, kj, max_len)
            return x, caches
        return (LoopSpec("unit", unit, (up_abs, x_abs), (up_sh, x_sh),
                         reps),)

    # decode
    max_len = shape.seq_len
    cu_specs = {f"p{j}": tf.block_cache_specs(cfg, kj, b, max_len)
                for j, kj in enumerate(cfg.pattern)}
    cu_abs = jax.tree.map(_cache_abs_of(cfg), cu_specs, is_leaf=is_spec)
    cu_sh = _sh_tree(logical_tree(cu_specs), _shapes_of(cu_specs),
                     mesh, rules)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)

    def unit(up, cache, x, pos):
        new = {}
        for j, kj in enumerate(cfg.pattern):
            x, new[f"p{j}"] = tf.block_decode(
                up[f"p{j}"], x, cfg, kj, cache[f"p{j}"], pos)
        return x, new
    return (LoopSpec("unit", unit, (up_abs, cu_abs, x_abs, pos_abs),
                     (up_sh, cu_sh, x_sh, None), reps),)


def encdec_loops(model, cfg, shape, mesh, rules, kind: str,
                 accum: int = 1):
    from repro.launch.cells import LoopSpec
    b = shape.global_batch // (accum if kind == "train" else 1)
    s_half = shape.seq_len // 2
    dt = jnp.dtype(cfg.dtype)

    enc_specs = ed.enc_block_specs(cfg)
    dec_specs = ed.dec_block_specs(cfg)
    eu_abs = abstract_params(enc_specs, jnp.dtype(cfg.param_dtype))
    du_abs = abstract_params(dec_specs, jnp.dtype(cfg.param_dtype))
    eu_sh = _sh_tree(logical_tree(enc_specs), _shapes_of(enc_specs),
                     mesh, rules)
    du_sh = _sh_tree(logical_tree(dec_specs), _shapes_of(dec_specs),
                     mesh, rules)
    x_enc = jax.ShapeDtypeStruct((b, s_half, cfg.d_model), dt)
    x_sh = NamedSharding(mesh, rules.spec_for(("batch", "seq", "embed"),
                                              x_enc.shape, mesh))
    loops = []

    if kind == "train":
        def enc_fwd(up, x):
            return ed.enc_block_apply(up, x, cfg).astype(jnp.float32).sum()

        def dec_fwd(up, x, eo):
            return ed.dec_block_apply(up, x, eo,
                                      cfg).astype(jnp.float32).sum()
        enc_ck, dec_ck = _remat(enc_fwd, cfg), _remat(dec_fwd, cfg)
        loops += [
            LoopSpec("enc_flops", lambda up, x: jax.grad(
                enc_ck, argnums=(0, 1))(up, x),
                (eu_abs, x_enc), (eu_sh, x_sh), cfg.enc_layers, ("flops",)),
            LoopSpec("enc_coll", lambda up, x: jax.grad(
                enc_ck, argnums=1)(up, x),
                (eu_abs, x_enc), (eu_sh, x_sh), cfg.enc_layers, ("coll",)),
            LoopSpec("dec_flops", lambda up, x, eo: jax.grad(
                dec_ck, argnums=(0, 1, 2))(up, x, eo),
                (du_abs, x_enc, x_enc), (du_sh, x_sh, x_sh),
                cfg.num_layers, ("flops",)),
            LoopSpec("dec_coll", lambda up, x, eo: jax.grad(
                dec_ck, argnums=(1, 2))(up, x, eo),
                (du_abs, x_enc, x_enc), (du_sh, x_sh, x_sh),
                cfg.num_layers, ("coll",)),
        ]
        return tuple(loops)

    if kind == "prefill":
        def enc_unit(up, x):
            return ed.enc_block_apply(up, x, cfg)

        def dec_unit(up, x, eo):
            # mirrors EncDecLM.prefill body (self prefill + cross kv)
            import repro.models.attention as attn
            from repro.models.layers import mlp_apply, rms_norm
            h = rms_norm(x, up["ln1"], cfg.norm_eps)
            y, self_cache = attn.attention_prefill(
                up["self_attn"], h, cfg, kind="global", cache_len=s_half)
            x = x + y
            h = rms_norm(x, up["ln_x"], cfg.norm_eps)
            ck = jnp.einsum("bse,ehd->bshd", eo,
                            up["cross_attn"]["wk"].astype(dt))
            cv = jnp.einsum("bse,ehd->bshd", eo,
                            up["cross_attn"]["wv"].astype(dt))
            x = x + attn.attention_apply(up["cross_attn"], h, cfg,
                                         kind="cross", x_kv=eo)
            h = rms_norm(x, up["ln2"], cfg.norm_eps)
            x = x + mlp_apply(up["ffn"], h, cfg)
            return x, (self_cache, ck, cv)
        loops += [
            LoopSpec("enc_unit", enc_unit, (eu_abs, x_enc), (eu_sh, x_sh),
                     cfg.enc_layers),
            LoopSpec("dec_unit", dec_unit, (du_abs, x_enc, x_enc),
                     (du_sh, x_sh, x_sh), cfg.num_layers),
        ]
        return tuple(loops)

    # decode: one token through a decoder block with self+cross caches
    from repro.models import attention as attn
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    cache_abs = {
        "self": jax.tree.map(_cache_abs_of(cfg),
                             attn.cache_specs(cfg, b, s_half),
                             is_leaf=is_spec),
        "cross_k": jax.ShapeDtypeStruct((b, s_half, kv, hd), dt),
        "cross_v": jax.ShapeDtypeStruct((b, s_half, kv, hd), dt),
    }
    cache_logical = {
        "self": logical_tree(attn.cache_specs(cfg, b, s_half)),
        "cross_k": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "cross_v": ("batch", "kv_seq", "kv_heads", "head_dim"),
    }
    cache_shapes = jax.tree.map(lambda a: a.shape, cache_abs)
    cache_sh = _sh_tree(cache_logical, cache_shapes, mesh, rules)
    x_dec = jax.ShapeDtypeStruct((b, 1, cfg.d_model), dt)
    x_dec_sh = NamedSharding(mesh, rules.spec_for(
        ("batch", "seq", "embed"), x_dec.shape, mesh))
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)

    def dec_unit(up, c, x, pos):
        from repro.models.layers import mlp_apply, rms_norm
        h = rms_norm(x, up["ln1"], cfg.norm_eps)
        y, self_cache = attn.decode_attention(up["self_attn"], h, cfg,
                                              c["self"], pos)
        x = x + y
        h = rms_norm(x, up["ln_x"], cfg.norm_eps)
        x = x + ed._cross_decode(up["cross_attn"], h, cfg,
                                 c["cross_k"], c["cross_v"])
        h = rms_norm(x, up["ln2"], cfg.norm_eps)
        x = x + mlp_apply(up["ffn"], h, cfg)
        return x, self_cache
    return (LoopSpec("dec_unit", dec_unit,
                     (du_abs, cache_abs, x_dec, pos_abs),
                     (du_sh, cache_sh, x_dec_sh, None), cfg.num_layers),)


def micro_loop(model, cfg, shape, mesh, rules, accum, batch_abs, batch_sh):
    """LoopSpec for the grad-accumulation microbatch scan body."""
    from repro.launch.cells import LoopSpec
    params_abs = abstract_params(model.specs(), jnp.dtype(cfg.param_dtype))
    params_sh = _sh_tree(logical_tree(model.specs()),
                         _shapes_of(model.specs()), mesh, rules)
    micro_abs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (s.shape[0] // accum,) + s.shape[1:], s.dtype), batch_abs)

    def micro_fn(params, mb):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, mb), has_aux=True)(params)
        return loss, grads
    # grads land in the carry with the params' sharding, forcing the same
    # per-microbatch DP reduction the real scan body performs.
    return LoopSpec("micro", _wrap_act(micro_fn, mesh, rules),
                    (params_abs, micro_abs),
                    (params_sh, batch_sh), accum, ("flops", "coll"),
                    out_shardings=(None, params_sh))


def loops_for(model, cfg, shape, mesh, rules, kind: str,
              accum: int = 1) -> Tuple[Any, ...]:
    if cfg.family == "encdec":
        loops = encdec_loops(model, cfg, shape, mesh, rules, kind, accum)
    else:
        loops = lm_loops(model, cfg, shape, mesh, rules, kind, accum)
    for lp in loops:
        lp.fn = _wrap_act(lp.fn, mesh, rules)
    return loops
