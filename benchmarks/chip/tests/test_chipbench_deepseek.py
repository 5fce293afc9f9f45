"""The DeepSeek-V2-Lite cell on the CPU: the reduced cell through the
harness's own run, the FLOP counts against a count by hand from the
published configuration, and the grouped-matmul roofline reader on a
trace written by hand."""
import os
import sys
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_cells  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402
from test_chipbench_trace import _plane  # noqa: E402

WORKLOAD = "deepseek-v2-lite.train"
NS = 1e-9


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the peaks table; give it one for the run."""
    real = peaks.lookup
    monkeypatch.setattr(peaks, "lookup", lambda kind: real("TPU v5 lite")
                        if kind == "cpu" else real(kind))


def test_reduced_cell_runs_and_is_correct(cpu_peaks):
    cell = chipbench_cells.reduced_cell(WORKLOAD)
    assert cell.per_layer == ["step_mfu", "idle_share", "gmm_roofline"]
    out = harness.run_cell(cell, seed=2**33 + 29, seconds=1.0, trace=False,
                           t_process=time.perf_counter(), reduced=True,
                           chip=False)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == set(cell.limits)
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    traced = harness.run_cell(cell, seed=2**31 + 5, seconds=0.2, trace=True,
                              t_process=time.perf_counter(), reduced=True,
                              chip=False)
    assert traced["correct"] is True, traced["checks"]
    assert {"step_mfu", "idle_share"} <= set(traced["metrics"])
    assert set(traced["metrics"]) <= {"step_mfu", "idle_share",
                                      "gmm_roofline"}
    for m in traced["metrics"].values():
        assert 0 < m["value"] < 100


def test_program_runs_the_configuration():
    cell = chipbench_cells.reduced_cell(WORKLOAD)
    harness.init_jax(cell, chip=False)
    trainer, _ = harness.build_trainer(cell, reduced=True)
    got = trainer.model.cfg
    assert (got.num_experts, got.held_experts) == (8, 4)
    assert harness.departures(trainer, cell) == []


def _full():
    cfg = harness.load_json("configs/deepseek-v2-lite.json")
    return cfg, harness.load_module(cfg["reference"])


def test_routed_flops_by_hand():
    cfg, ref = _full()
    # 4 MoE layers; a token meets 6 * 8 / 64 = 0.75 held experts of
    # 3 * 2,048 * 1,408 weights, 6 FLOPs a weight
    per_expert = 3 * 2048 * 1408                  # 8,650,752
    assert ref.routed_flops_per_token(cfg) == pytest.approx(
        4 * 6 * 0.75 * per_expert)
    assert ref.routed_flops_per_token(cfg) == 155_713_536


def test_flops_by_hand():
    cfg, ref = _full()
    mla = (2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256
           + 16 * 128 * 2048)                     # 13,762,560
    assert mla == 13_762_560
    dense_mlp = 3 * 2048 * 10944
    moe_layer = 2048 * 64 + 3 * 2048 * 2816       # router, shared experts
    head = 2048 * 12800
    weights = 5 * mla + dense_mlp + 4 * moe_layer + head
    attention = 5 * 3 * 2 * 16 * (192 + 128) * (8192 + 1) / 2
    want = 6 * weights + attention + 155_713_536
    assert ref.flops_per_token(cfg, 8192) == pytest.approx(want)
    assert ref.flops_per_token(cfg, 8192) == pytest.approx(2.1769e9,
                                                           rel=1e-4)
    # MLA's projections and attention core are about half of it
    assert (6 * 5 * mla + attention) / want == pytest.approx(0.479,
                                                             abs=1e-3)


TEXT = (
    _plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_train_step", 0, 400),
                         ("jit_train_step", 500, 900)]),
        ("XLA Ops", [
            ("%ragged-dot-metadata.1 = (s32[9]) custom-call(%g)", 0, 20),
            ("%ragged-dot-none.1 = bf16[8,16] custom-call(%a)", 20, 120),
            ("%fusion.4 = bf16[8,16] fusion(%b)", 120, 400),
            ("%ragged-dot-metadata.2 = (s32[9]) custom-call(%g)", 500, 510),
            ("%ragged-dot-none.7 = bf16[8,16] custom-call(%c)", 510, 660),
            ("%fusion.9 = bf16[8,16] fusion(%d)", 660, 900)])])
    + _plane(2, "/host:CPU", [("python", [("window", 0, 1000)])]))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    return trace_reduce.load(str(path))


def _ctx(trace, reference):
    cell = types.SimpleNamespace(reference=reference, config={},
                                 mix={"seq_len": 4, "global_batch": 1})
    return harness.Context(cell=cell, segs=[], dmr_history=[],
                           device_kind="TPU v5 lite", trace=trace,
                           slices0=1)


def test_gmm_roofline_on_a_trace_by_hand(trace):
    gmm = harness.load_module("layer_metrics/gmm_roofline.py")
    # the grouped matmuls: 100 + 150 ns; the metadata and fusions left out
    assert gmm.gmm_s(trace) == pytest.approx(250 * NS)
    ref = types.SimpleNamespace(routed_flops_per_token=lambda c: 1e6)
    # 2 steps of 4 tokens at 1e6 routed FLOPs over 250 ns at 197 TFLOP/s
    assert gmm.read(_ctx(trace, ref)) == (
        pytest.approx(100 * 8e6 / (250e-9 * 197e12)), "%")
    # a reference that counts no routed experts reads nothing
    assert gmm.read(_ctx(trace, types.SimpleNamespace())) is None
