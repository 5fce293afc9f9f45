"""The harness's own memory on the chip, held by what the CPU can show.

``references/train.py`` donates the parameters, the gradient and the two
moments from call to call and keeps the starting point on the host, so that
the reference fits beside a configuration whose program state fills a chip.
None of that may change a reading.  At the program's reduced sizes, for every
configuration under ``configs/``:

- the reference's readings equal, bit for bit, those of the plain loop
  written here (nothing donated, the start kept on the device, norms over
  whole trees), for the reference itself, the control and a planted fault;
- the compiled update hands each of its four trees' buffers to the tree
  that succeeds it;
- ``harness.delta_norm_fn`` gives the norms of the change computed plainly
  (it holds no copy of the parameters on the chip beyond a leaf's: the
  compiler fuses the start it makes again into the norms; see
  ``compile_rehearsal.py``).
"""
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_cells  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402
from references import train as ref_train  # noqa: E402

F32 = jnp.float32
MODES = {"reference": {}, "control": {"param_dtype": jnp.bfloat16},
         "half_batch": {"rows": lambda k: slice(0, 2)}}


def reduced(config: str):
    return chipbench_cells.reduce(
        chipbench_cells.config_cell(config, "steady_b8_s2048"))


def plain_train(model, cfg, opt, params, batches, *, param_dtype=F32,
                rows=None):
    """The reference's training as a plain loop that donates nothing."""
    b1, b2, eps, wd = (opt[k] for k in ("beta1", "beta2", "eps",
                                         "weight_decay"))
    names = ref_train.leaf_names(params)
    decay = [ref_train.decays(n, p.shape)
             for n, p in zip(names, jax.tree.leaves(params))]

    @jax.jit
    def grad_fn(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            def body(carry, row):
                tot, g = carry
                v, gr = jax.value_and_grad(model.token_nll_sum)(
                    params, row[0][None], row[1][None], cfg)
                return (tot + v, jax.tree.map(jnp.add, g, gr)), None
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            (tot, g), _ = jax.lax.scan(body, (jnp.zeros((), F32), zero),
                                       (tokens, labels))
            n = tokens.size
            return tot / n, jax.tree.map(lambda x: x / n, g)

    @jax.jit
    def update(params, grads, mu, nu, lr, t):
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        pl, treedef = jax.tree.flatten(params)
        out = []
        for p, g, m, v, dec in zip(pl, jax.tree.leaves(grads),
                                   jax.tree.leaves(mu), jax.tree.leaves(nu),
                                   decay):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            p32 = p.astype(F32)
            if dec:
                u = u + wd * p32
            out.append(((p32 - lr * u).astype(param_dtype), m, v))
        return ([jax.tree.unflatten(treedef, [o[i] for o in out])
                 for i in range(3)] + [grads])

    start = params
    params = jax.tree.map(lambda p: p.astype(param_dtype), params)
    mu = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    nu = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses = []
    for k, batch in enumerate(batches):
        sl = rows(k) if rows is not None else slice(None)
        p32 = jax.tree.map(lambda p: p.astype(F32), params)
        loss, grads = grad_fn(p32, batch["tokens"][sl], batch["labels"][sl])
        losses.append(float(loss))
        params, mu, nu, clipped = update(
            params, grads, mu, nu, jnp.float32(ref_train.lr_at(opt, k + 1)),
            jnp.float32(k + 1))
        if k == 0:
            first = (ref_train.unit_norms(clipped),
                     ref_train.unit_norms(grads))
    delta = jax.tree.map(lambda a, b: a.astype(F32) - b, params, start)
    return {"losses": losses, "grad": first[0], "grad_raw": first[1],
            "delta": ref_train.unit_norms(delta)}


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("config", chipbench_cells.configs())
def test_train_equals_plain_loop(config, mode):
    cell = reduced(config)
    ref, cfg, mix = cell.reference, cell.config, cell.mix
    key = traffic.seed_key(2**33 + 5, traffic.WEIGHTS)
    feed = traffic.TokenFeed(2**33 + 5, cfg["vocab_size"], mix["seq_len"],
                             mix["global_batch"])
    batches = [feed.at(k) for k in range(mix["setup_steps"])]
    init = jax.jit(lambda k: ref.init_params(k, cfg))
    want = plain_train(ref, cfg, mix["optimizer"], init(key), batches,
                       **MODES[mode])
    params = init(key)
    got = ref_train.train(ref, cfg, mix["optimizer"], params, batches,
                          **MODES[mode])
    assert got == want
    assert all(math.isfinite(v) for v in got["delta"].values())
    assert all(p.is_deleted() for p in jax.tree.leaves(params))


@pytest.mark.parametrize("param_dtype", [F32, jnp.bfloat16])
def test_update_hands_each_tree_to_its_successor(param_dtype):
    cell = reduced("smollm-135m")
    ref, cfg = cell.reference, cell.config
    params = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    held = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, param_dtype),
                        params)
    decay = [ref_train.decays(n, p.shape) for n, p in
             zip(ref_train.leaf_names(params), jax.tree.leaves(params))]
    update = ref_train.make_update(cell.mix["optimizer"], decay, param_dtype)
    scalar = jax.ShapeDtypeStruct((), F32)
    text = update.lower(held, params, params, params, scalar,
                        scalar).as_text()
    main = next(ln for ln in text.splitlines() if "@main(" in ln)
    aliased = {int(a): int(o) for a, o in re.findall(
        r"%arg(\d+): [^%]*?tf\.aliasing_output = (\d+)", main)}
    n = len(jax.tree.leaves(params))
    # arguments and results both run params, grads, mu, nu, leaf by leaf
    assert aliased == {i: i for i in range(4 * n)}


@pytest.mark.parametrize("config", chipbench_cells.configs())
def test_delta_norm_fn_is_the_plain_change(config):
    cell = reduced(config)
    ref, cfg = cell.reference, cell.config
    key = traffic.seed_key(2**34 + 9, traffic.WEIGHTS)
    # the weights as ``make_state`` makes them
    start = jax.jit(lambda k: ref.to_program(ref.init_params(k, cfg), cfg))(
        key)
    leaves, tree = jax.tree.flatten(start)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    moved = jax.tree.unflatten(tree, [p + 1e-3 * jax.random.normal(k, p.shape)
                                      for p, k in zip(leaves, keys)])
    got = ref_train.expand(harness.delta_norm_fn(cell)(moved, key))
    plain = ref.from_program(jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        moved, start), cfg)
    want = {}
    for name, d in zip(ref_train.leaf_names(plain), jax.tree.leaves(plain)):
        axes = tuple(range(1, d.ndim)) if name.startswith("layers.") \
            else None
        v = np.sqrt(np.sum(d * d, axis=axes))
        want.update({f"{name}.{i}": float(x) for i, x in enumerate(v)}
                    if v.ndim else {name: float(v)})
    assert got.keys() == want.keys()
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-5), name
    # the start is made again in a program of its own, where the compiler
    # may round ``std * z`` otherwise (a fused multiply-add): the weights
    # left as they are read rounding, not 0
    still = ref_train.expand(harness.delta_norm_fn(cell)(start, key))
    assert max(still.values()) < 1e-3 * min(want.values())
