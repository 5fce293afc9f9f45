"""CPU rehearsal of every cell through the harness's own functions.

The one-chip cells run in this process at the program's reduced sizes;
the four-chip elastic cell runs in a child process on four virtual CPU
devices (as ``tests/test_multidevice.py`` does), since the test process
must keep its single CPU device.  Nothing here loads the TPU's library.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_cells  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402

ROOT = chipbench_cells.ROOT
BENCH = chipbench_cells.BENCH
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the peaks table; give it one for the run."""
    real = peaks.lookup
    monkeypatch.setattr(peaks, "lookup", lambda kind: real("TPU v5 lite")
                        if kind == "cpu" else real(kind))


def test_every_cell_loads_from_data():
    bench = harness.benchmark()
    assert bench["command"][1] == "benchmarks/chip/run.py"
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        assert callable(cell.reference.flops_per_token)
        for name in cell.per_layer:
            mod = harness.load_module(f"layer_metrics/{name}.py")
            assert callable(mod.read)
        assert cell.limits and set(cell.limits) <= {
            "loss_gap", "loss_gap_first", "grad_gap", "grad_gap_median",
            "update_gap",
            "reshard_mismatch"}
    names = {m["name"] for m in bench["end_to_end"]}
    assert {"setup_s", "train_tokens_per_s", "reconfig_s"} <= names


@pytest.mark.parametrize("workload", ["smollm-135m.train"])
def test_one_chip_cell_runs_and_is_correct(workload, cpu_peaks):
    cell = chipbench_cells.reduced_cell(workload)
    out = harness.run_cell(cell, seed=2**33 + 17, seconds=1.0, trace=False,
                           t_process=time.perf_counter(), reduced=True,
                           chip=False)
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 1
    assert out["device"]["memory_peak_bytes"] > 0
    traced = harness.run_cell(cell, seed=5, seconds=0.2, trace=True,
                              t_process=time.perf_counter(), reduced=True,
                              chip=False)
    assert traced["correct"] is True
    assert set(traced["metrics"]) == {"step_mfu", "idle_share"}
    assert 0 < traced["metrics"]["step_mfu"]["value"] < 100
    assert traced["device"]["busy_s"] > 0
    assert traced["device"]["window_s"] >= 0.2
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(traced["breakdown"]["device_ops"]) <= 10


def test_options_reach_the_program():
    cell = chipbench_cells.reduced_cell("smollm-135m.train")
    harness.init_jax(cell, chip=False)
    trainer, _ = harness.build_trainer(cell, reduced=True)
    assert trainer.model.cfg.norm_eps == cell.config["rms_norm_eps"] == 1e-5
    assert harness.departures(trainer, cell) == []


def test_every_departure_is_reported():
    """Mamba2-130M states a float32 residual stream, for which the program
    has no option; the harness refuses to run it and says so."""
    cell = chipbench_cells.reduce(
        chipbench_cells.config_cell("mamba2-130m", "steady_b8_s2048"))
    harness.init_jax(cell, chip=False)
    with pytest.raises(harness.BadRun, match="residual_in_fp32") as e:
        harness.build_trainer(cell, reduced=True)
    assert "norm_eps" not in str(e.value)


def test_same_seed_same_inputs():
    import traffic
    a = traffic.TokenFeed(2**40 + 3, 1000, 16, 2).at(5)
    b = traffic.TokenFeed(2**40 + 3, 1000, 16, 2).at(5)
    c = traffic.TokenFeed(3, 1000, 16, 2).at(5)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()


def test_segments_hold_each_resize():
    import traffic
    arrive, leave = traffic.cycle_events(3, 5, 20, 20, cycles=2)
    assert (arrive, leave) == ([25, 65], [45, 85])
    plan = list(traffic.plan_segments(3, 50, arrive + leave, 20))
    assert plan == [(3, 23, False), (23, 24, False), (24, 26, True),
                    (26, 44, False), (44, 46, True), (46, 50, False)]


ELASTIC = """
import json, sys, time
sys.path.insert(0, {here!r})
import chipbench_cells, harness
cell = chipbench_cells.reduced_cell("smollm-135m.elastic4")
harness.init_jax(cell, chip=False)
job = harness.start_job(cell, reduced=True)
state, prog = harness.first_steps(job, cell, 2**35 + 1)
state, segs, window, log = harness.run_window(
    job.trainer, cell, job.rec, job.hook, state, 2.0)
out = harness.run_cell(cell, seed=9, seconds=2.0, trace=False,
                       t_process=time.perf_counter(), reduced=True,
                       chip=False)
print(json.dumps({{"setup": prog["resizes"],
                  "window": [[e["action"], e["from"], e["to"]] for e in log],
                  "stalls": harness.reconfig_stalls(segs),
                  "result": out}}))
"""


def test_elastic_cell_on_four_cpu_devices():
    env = _env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", ELASTIC.format(here=HERE)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["setup"] == [[4, 2], [2, 4]]
    actions = [tuple(a) for a in got["window"]]
    assert ("SHRINK", 4, 2) in actions and ("EXPAND", 2, 4) in actions
    assert actions[0] == ("SHRINK", 4, 2)
    assert len(got["stalls"]) == len(actions)
    out = got["result"]
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["reshard_mismatch"]["value"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "reconfig_s",
                                   "setup_s"}
    assert out["device"]["count"] == 4


def test_command_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-135m.train", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_command_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = _env(PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-135m.train", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
