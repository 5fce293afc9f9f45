"""The plain references against the program's models, and the FLOP
counts against a count by hand from the published configurations.

At the program's reduced sizes, in float32 with full-precision matmuls on
both sides, the reference's loss and gradients must equal the program's
model to float32 rounding: the two are independent implementations of one
architecture (the reference shares no code with ``repro.models``).
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_cells  # noqa: E402
import harness  # noqa: E402
from references import train as ref_train  # noqa: E402

CASES = [(c, harness.load_json(f"configs/{c}.json")["program"]["arch"])
         for c in chipbench_cells.configs()]


def reduced(config: str):
    cell = chipbench_cells.reduce(
        chipbench_cells.config_cell(config, "steady_b8_s2048"))
    return cell.config, cell.reference


@pytest.mark.parametrize("config,arch", CASES)
def test_reference_matches_program_model(config, arch):
    from repro.models import build_model, get_model, reduced_config
    cfg, ref = reduced(config)
    _, full = get_model(arch)
    opts = {f: cfg[k] for f, k in cfg["program"]["options"].items()}
    pcfg = dataclasses.replace(reduced_config(full), dtype="float32", **opts)
    model = build_model(pcfg)
    key = jax.random.PRNGKey(3)
    params = ref.init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0,
                                cfg["vocab_size"])
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

    with jax.default_matmul_precision("highest"):
        (loss_p, _), g_p = jax.value_and_grad(model.loss, has_aux=True)(
            ref.to_program(params, cfg), batch)
        loss_r, g_r = ref_train.make_grad_fn(ref, cfg)(
            params, batch["tokens"], batch["labels"],
            ref_train.zeros(params))
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    got = ref_train.unit_norms(ref.from_program(g_p, cfg))
    want = ref_train.unit_norms(g_r)
    assert got.keys() == want.keys()
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-3, abs=1e-7), \
            name
    diff = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                        ref.from_program(g_p, cfg), g_r)
    scale = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(g_r))
    assert max(jax.tree.leaves(diff)) < 1e-4 * scale


@pytest.mark.parametrize("config,arch", CASES)
def test_layout_maps_round_trip(config, arch):
    cfg, ref = reduced(config)
    p = ref.init_params(jax.random.PRNGKey(0), cfg)
    back = ref.from_program(ref.to_program(p, cfg), cfg)
    for name, a, b in zip(ref_train.leaf_names(p), jax.tree.leaves(p),
                          jax.tree.leaves(back)):
        shift = 1.0 if name in ref.NORMS else 0.0   # norm offsets
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b) + shift)


def test_weight_decay_on_matrices_alone():
    want = {"smollm-135m": {"embed", "layers.wq", "layers.wk", "layers.wv",
                            "layers.wo", "layers.w_gate", "layers.w_up",
                            "layers.w_down"},
            "mamba2-130m": {"embed", "layers.in_proj", "layers.conv_w",
                            "layers.out_proj"}}
    for config, names in want.items():
        cfg, ref = reduced(config)
        p = ref.init_params(jax.random.PRNGKey(0), cfg)
        got = {n for n, x in zip(ref_train.leaf_names(p), jax.tree.leaves(p))
               if ref_train.decays(n, x.shape)}
        assert got == names, config


def test_smollm_flops_by_hand():
    cfg = harness.load_cell("smollm-135m.train").config
    ref = harness.load_module(cfg["reference"])
    per_layer = 576 * (576 + 2 * 192) + 576 * 576 + 3 * 576 * 1536
    weights = 30 * per_layer + 49152 * 576        # 134,479,872
    attention = 30 * 3 * 2 * 2 * 576 * (2048 + 1) / 2
    assert weights == 134_479_872
    assert ref.flops_per_token(cfg, 2048) == pytest.approx(
        6 * weights + attention)
    assert ref.flops_per_token(cfg, 2048) == pytest.approx(1.0193e9,
                                                           rel=1e-4)


def test_mamba2_flops_by_hand():
    cfg = harness.load_json("configs/mamba2-130m.json")
    ref = harness.load_module(cfg["reference"])
    d_in = 2 * 768
    per_layer = (768 * (2 * d_in + 2 * 128 + 24) + d_in * 768
                 + 4 * (d_in + 2 * 128))
    weights = 24 * per_layer + 50288 * 768        # 128,888,832
    ssd = 257 / 2 * 128 + 257 / 2 * d_in + 2 * 128 * d_in
    assert weights == 128_888_832
    assert ref.flops_per_token(cfg, 2048) == pytest.approx(
        6 * weights + 24 * 3 * 2 * ssd)
