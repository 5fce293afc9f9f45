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
