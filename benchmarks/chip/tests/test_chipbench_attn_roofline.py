"""The fused attention core's roofline reader: its FLOP count for the
DeepSeek-V2-Lite cell against a count by hand, and its reading of a trace
written by hand, with and without the kernel's operations."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_cells  # noqa: E402,F401  (puts the harness on the path)
import harness  # noqa: E402
import trace_reduce  # noqa: E402
from test_chipbench_trace import _plane  # noqa: E402

NS = 1e-9
attn = harness.load_module("layer_metrics/attn_roofline.py")


def test_core_flops_by_hand():
    cfg = harness.load_json("configs/deepseek-v2-lite.json")
    # 5 layers of 16 heads; query-key 128 + 64, value 128; a token at
    # position i meets i + 1 keys, (S + 1) / 2 on average; 2 FLOPs a
    # multiply-add, forward and backward 3 x the forward
    per_layer = 3 * 2 * 16 * (128 + 64 + 128) * 8193 / 2     # 125,844,480
    assert per_layer == 125_844_480
    assert attn.core_flops_per_token(cfg, 8192) == pytest.approx(
        5 * per_layer)
    assert attn.core_flops_per_token(cfg, 8192) == 629_222_400
    # 29% of the model FLOPs of a token
    ref = harness.load_module(cfg["reference"])
    assert attn.core_flops_per_token(cfg, 8192) / ref.flops_per_token(
        cfg, 8192) == pytest.approx(0.289, abs=1e-3)
    smollm = harness.load_json("configs/smollm-135m.json")
    assert attn.core_flops_per_token(smollm, 2048) is None


def _trace(tmp_path, ops):
    from jax.profiler import ProfileData
    text = (_plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_train_step", 0, 400),
                         ("jit_train_step", 500, 900)]),
        ("XLA Ops", ops)])
        + _plane(2, "/host:CPU", [("python", [("window", 0, 1000)])]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.load(str(path))


def _ctx(trace, config):
    cell = types.SimpleNamespace(config=config,
                                 mix={"seq_len": 7, "global_batch": 2})
    return harness.Context(cell=cell, segs=[], dmr_history=[],
                           device_kind="TPU v5 lite", trace=trace,
                           slices0=1)


CONFIG = {"num_hidden_layers": 2, "num_attention_heads": 4,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16}


def test_attn_roofline_on_a_trace_by_hand(tmp_path):
    trace = _trace(tmp_path, [
        ("%splash_mha_fwd_residuals.2 = (f32[2,512,128]) custom-call(%a)",
         0, 40),
        ("%splash_mha_fwd_residuals.3 = (f32[2,512,128]) custom-call(%a)",
         40, 70),
        ("%splash_mha_dq_no_residuals.1 = (f32[2,512,192]) custom-call(%b)",
         70, 120),
        ("%splash_mha_dkv_no_residuals.1 = (f32[2,512,192]) "
         "custom-call(%b)", 120, 200),
        ("%fusion.4 = bf16[8,16] fusion(%b)", 200, 400),
        ("%splash_mha_fwd_no_residuals.7 = bf16[2,4,7,16] custom-call(%c)",
         500, 560),
        ("%vmap_jit__splash_attention__.8 = bf16[2,4,7,16] fusion(%c)",
         560, 600),
        ("%fusion.9 = bf16[8,16] fusion(%d)", 600, 900)])
    # the kernels: 40 + 30 + 50 + 80 + 60 ns; fusions around them left out
    assert attn.kernel_s(trace) == pytest.approx(260 * NS)
    # 2 layers x 3 x 2 x 4 heads x 40 wide x (7 + 1) / 2 = 7,680 a token;
    # 2 steps of 2 x 7 tokens over 260 ns at 197 TFLOP/s
    assert attn.core_flops_per_token(CONFIG, 7) == 7680
    assert attn.read(_ctx(trace, CONFIG)) == (
        pytest.approx(100 * 2 * 14 * 7680 / (260e-9 * 197e12)), "%")


def test_attn_roofline_reads_nothing_without_the_kernel(tmp_path):
    trace = _trace(tmp_path, [("%fusion.4 = bf16[8,16] fusion(%b)", 0, 400),
                              ("%fusion.9 = bf16[8,16] fusion(%d)", 500,
                               900)])
    assert attn.kernel_s(trace) == 0
    assert attn.read(_ctx(trace, CONFIG)) is None
    # nor for a configuration without latent attention
    kernel = _trace(tmp_path, [
        ("%splash_mha_fwd_residuals.2 = (f32[2,512,128]) custom-call(%a)",
         0, 40)])
    assert attn.read(_ctx(kernel, {"num_hidden_layers": 2})) is None
