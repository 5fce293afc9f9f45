"""Compile the main path's kernels and the decode step for a described TPU
v5e chip, at real widths.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached: these tests catch what interpret mode cannot (tile
alignment, fast-memory limits, programs that do not fit the device) with no
chip.  The topology is described inside a fixture, never at import, because
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rglru.kernel import rglru_scan_pallas
from repro.kernels.ssd.kernel import ssd_scan
from repro.models import get_model

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("heads,kv_heads,d,window,softcap", [
    (9, 3, 64, None, None),         # SmolLM-135M
    (8, 4, 128, 1024, 50.0),        # width 128, sliding window, softcap
])
def test_flash_attention_compiles(one_chip, heads, kv_heads, d, window,
                                  softcap):
    compiled = compile_for(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, window=window,
                                        softcap=softcap),
        ((2, heads, 2048, d), BF16), ((2, kv_heads, 2048, d), BF16),
        ((2, kv_heads, 2048, d), BF16))
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """Mamba2-130M: 24 heads of width 64, state 128, chunk 128."""
    compiled = compile_for(
        one_chip, lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=128),
        ((2, 2048, 24, 64), BF16), ((2, 2048, 24), F32), ((24,), F32),
        ((2, 2048, 128), BF16), ((2, 2048, 128), BF16))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_rglru_scan_compiles_at_width_4096(one_chip, dtype):
    compiled = compile_for(one_chip, rglru_scan_pallas,
                           ((2, 2048, 4096), dtype), ((2, 2048, 4096), dtype))
    assert "tpu_custom_call" in compiled.as_text()


def test_smollm_decode_step_fits_one_chip(one_chip):
    """The SmolLM-135M decode step at batch 8 and a 2048-token cache."""
    model, _ = get_model("smollm-135m")
    place = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(8, 2048)))
    token = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(params, cache, token,
                                                pos).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES


# -- multi-head latent attention's core: the fused kernel where the step is
# -- compiled for a TPU, the chunked core elsewhere


def _train_step_text(one_chip, arch, seq_len):
    """The reduced ``arch``'s train step, built through the launcher as the
    benchmark builds it and compiled for the described chip on the
    trainer's own one-device mesh."""
    from repro.core import make_mesh
    from repro.launch.train import build
    trainer, _ = build(arch, reduced=True, seq_len=seq_len, global_batch=2)
    dev = next(iter(one_chip.device_set))
    mesh = make_mesh(1, 1, devices=[dev])
    trainer.devices, trainer.mesh = [dev], mesh
    shardings = trainer._state_shardings(mesh)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        jax.eval_shape(lambda: trainer._fresh_state(0)), shardings)
    bs = trainer._batch_shardings(mesh)
    batch = {k: jax.ShapeDtypeStruct((2, seq_len), jnp.int32,
                                     sharding=bs[k])
             for k in ("tokens", "labels")}
    with mesh:
        return trainer.step_fn(mesh).lower(state, batch).compile().as_text()


@pytest.mark.parametrize("arch,fused", [("deepseek-v2-lite", True),
                                        ("smollm-135m", False)])
def test_step_runs_the_fused_core_where_attention_is_latent(one_chip, arch,
                                                            fused):
    """DeepSeek-V2-Lite's step holds the splash kernels (forward, dq, dkv);
    SmolLM's grouped-query attention keeps the chunked core, no kernel."""
    text = _train_step_text(one_chip, arch, 256)
    for phase in ("fwd", "dq", "dkv"):
        assert (f"splash_mha_{phase}" in text) == fused
    assert ("tpu_custom_call" in text) == fused


@pytest.mark.parametrize("seq,fused", [(384, True), (200, False)])
def test_mla_forward_on_the_chip_by_sequence_length(one_chip, seq, fused):
    """Traced for the chip, MLA takes the kernel for sequences of whole
    128-wide lanes (blocks of 128 for 384) and the chunked core for the
    rest."""
    import dataclasses

    from jax.sharding import Mesh

    from repro.core.sharding import tracing_mesh
    from repro.models import reduced_config
    from repro.models.attention import mla_apply, mla_specs
    from repro.models.layers import init_from_specs
    cfg = dataclasses.replace(reduced_config(get_model("deepseek-v2-lite")[1]),
                              attn_chunk=40)
    params = jax.eval_shape(lambda: init_from_specs(jax.random.PRNGKey(0),
                                                    mla_specs(cfg)))
    dev = next(iter(one_chip.device_set))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((2, seq, cfg.d_model), BF16, sharding=one_chip)
    with tracing_mesh(Mesh([dev], ("data",))):
        compiled = jax.jit(lambda p, x: mla_apply(p, x, cfg)).lower(
            params, x).compile()
    assert ("splash_mha_fwd" in compiled.as_text()) == fused


def test_fused_core_compiles_at_the_cell_widths(one_chip):
    """The core of ``deepseek-v2-lite.train``: 2 rows of 8,192 tokens, 16
    heads, query-key width 192, value width 128, bf16, forward and
    backward, in well under a gigabyte of temporaries."""
    from repro.models.attention import fused_causal_attention

    def loss(q, k, v):
        return jnp.sum(fused_causal_attention(q, k, v, 0.1147)
                       .astype(F32))
    compiled = compile_for(
        one_chip, jax.grad(loss, (0, 1, 2)),
        ((2, 8192, 16, 192), BF16), ((2, 8192, 16, 192), BF16),
        ((2, 8192, 16, 128), BF16))
    text = compiled.as_text()
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mha_{phase}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_mla_forward_lowers_without_the_kernel_on_cpu():
    """On the CPU, outside a mesh and under a CPU mesh alike, MLA's core
    is the chunked one: nothing of the kernel is lowered."""
    import dataclasses

    from repro.core import make_mesh
    from repro.core.sharding import tracing_mesh
    from repro.models import reduced_config
    from repro.models.attention import mla_apply, mla_specs
    from repro.models.layers import init_from_specs
    cfg = reduced_config(get_model("deepseek-v2-lite")[1])
    cfg = dataclasses.replace(cfg, attn_chunk=64)
    params = init_from_specs(jax.random.PRNGKey(0), mla_specs(cfg))
    x = jnp.zeros((2, 256, cfg.d_model), BF16)
    loss = jax.grad(lambda p, x: jnp.sum(mla_apply(p, x, cfg)
                                         .astype(F32)))
    texts = [jax.jit(loss).lower(params, x).as_text()]
    with tracing_mesh(make_mesh(1, 1)):
        texts.append(jax.jit(loss).lower(params, x).as_text())
    for text in texts:
        assert "tpu_custom_call" not in text and "splash" not in text
