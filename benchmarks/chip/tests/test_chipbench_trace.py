"""The trace reduction, the per-layer readers and the peaks table on a
small trace written by hand, against numbers worked out by hand.

Two chips, a window of 1,000 ns; operation names as the TPU writes them
(``%name = shape op(...)``), a loop around chip 0's first step.  The job holds both until the layout
marker at 500 ns, then only chip 0.  Chip 0 runs two train steps
([0, 250] and [600, 880]) and a copy program ([380, 450]); chip 1 runs one
train step ([0, 280]).
"""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_cells  # noqa: E402,F401  (puts the benchmark on sys.path)
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402

NS = 1e-9


def _events(evs, names):
    out = []
    for name, start, end, *stats in evs:
        st = "".join(f" stats {{ metadata_id: 1 int64_value: {v} }}"
                     for v in stats)
        out.append(f"events {{ metadata_id: {names[name]} "
                   f"offset_ps: {start * 1000} "
                   f"duration_ps: {(end - start) * 1000}{st} }}")
    return "\n".join(out)


def _plane(pid, name, lines):
    names = {}
    for _, evs in lines:
        for e in evs:
            names.setdefault(e[0], len(names) + 1)
    body = "".join(
        f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0 "
        f"{_events(evs, names)} }}\n" for i, (ln, evs) in enumerate(lines))
    meta = "".join(f"event_metadata {{ key: {k} value {{ id: {k} "
                   f"name: \"{n}\" }} }}\n" for n, k in names.items())
    stat = "stat_metadata { key: 1 value { id: 1 name: \"slices\" } }"
    return f"planes {{ id: {pid} name: \"{name}\"\n{body}{meta}{stat} }}\n"


TEXT = (
    _plane(1, "/device:TPU:0", [
        ("XLA Modules", [("jit_train_step", 0, 250), ("jit_copy", 380, 450),
                         ("jit_train_step", 600, 880)]),
        ("XLA Ops", [("%while.3 = (s32[]) while(%t)", 0, 250),
                     ("%fusion.1 = f32[8] fusion(%p)", 0, 200),
                     ("all-reduce.1", 200, 250), ("copy.1", 380, 450), ("fusion.2", 600, 800),
                     ("all-reduce.2", 800, 880)])])
    + _plane(2, "/device:TPU:1", [
        ("XLA Modules", [("jit_train_step", 0, 280)]),
        ("XLA Ops", [("fusion.1", 0, 200), ("all-reduce.1", 200, 280)])])
    + _plane(3, "/host:CPU", [
        ("python", [("window", 0, 1000), ("segment", 0, 500),
                    ("maybe_reconfigure", 440, 610), ("layout", 500, 500, 1),
                    ("segment", 500, 1000), ("sync", 860, 1000)])]))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TEXT))
    return trace_reduce.load(str(path))


def _ctx(trace):
    cell = types.SimpleNamespace(
        reference=types.SimpleNamespace(flops_per_token=lambda c, s: 1e6),
        config={}, mix={"seq_len": 1, "global_batch": 1})
    return harness.Context(cell=cell, segs=[], dmr_history=[],
                           device_kind="TPU v5 lite", trace=trace,
                           slices0=2)


def test_layout_and_window(trace):
    assert sorted(trace.devices) == [0, 1]
    assert trace.window() == (0.0, 1000.0)
    held = trace_reduce.held_intervals(trace, 2)
    assert held == {0: [(0.0, 1000.0)], 1: [(0.0, 500.0)]}


def test_busy_and_idle(trace):
    held = trace_reduce.held_intervals(trace, 2)
    # chip 0 busy 250 + 70 + 280 = 600, chip 1 280
    assert trace_reduce.busy_s(trace, held) == pytest.approx(440 * NS)
    # held 1000 + 500; busy inside it 600 + 280
    assert trace_reduce.idle_share(trace, held) == pytest.approx(
        1 - 880 / 1500)
    idle = harness.load_module("layer_metrics/idle_share.py").read(
        _ctx(trace))
    assert idle == (pytest.approx(100 * (1 - 880 / 1500)), "%")


def test_collectives_within_the_step(trace):
    # all-reduce 50 + 80 on chip 0, 80 on chip 1; steps 250 + 280 + 280
    coll, step = trace_reduce.collective_s(trace)
    assert coll == pytest.approx(210 * NS)
    assert step == pytest.approx(810 * NS)
    share = harness.load_module("layer_metrics/allreduce_share.py").read(
        _ctx(trace))
    assert share == (pytest.approx(100 * 210 / 810), "%")


def test_step_mfu(trace):
    # 2 steps of 1 token at 1e6 FLOPs over 810 ns of step time at 197 TFLOP/s
    mfu = harness.load_module("layer_metrics/step_mfu.py").read(_ctx(trace))
    assert mfu == (pytest.approx(100 * 2e6 / (810e-9 * 197e12)), "%")


def test_breakdown(trace):
    b = trace_reduce.breakdown(trace)
    ops = dict(b["device_ops"])
    assert "while.3" not in ops      # a loop's time is its body's
    assert ops["fusion.1"] == pytest.approx(200 * NS)
    assert ops["fusion.2"] == pytest.approx(100 * NS)
    assert ops["all-reduce.1"] == pytest.approx(65 * NS)
    assert ops["all-reduce.2"] == pytest.approx(40 * NS)
    assert ops["copy.1"] == pytest.approx(35 * NS)
    assert [g[0] for g in b["idle_gaps"]] == [
        "maybe_reconfigure", "segment", "sync"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [150 * NS, 130 * NS, 120 * NS])


def test_peaks_table():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
