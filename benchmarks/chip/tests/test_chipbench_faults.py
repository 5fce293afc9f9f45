"""The comparison that decides ``correct`` must fail what it exists to
catch.  At the program's reduced sizes on the CPU, with each cell's own
limits (``limits/<workload>.json``):

- the control: the reference put in the program's place with its
  parameters held in bfloat16, the next precision below the float32 the
  configurations state for parameters and optimizer state;
- the run itself, skipping only the look for a chip, with the timed path
  broken underneath the harness: a step that returns its state unchanged;
  half of each batch left out, the mean taken over the rest; and, for the
  elastic cell, the exchange between chips left out (each step sees only
  the first slice's rows, as a chip that skipped the all-reduce would).
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import chipbench_cells  # noqa: E402
import calibrate  # noqa: E402
import compare  # noqa: E402
import harness  # noqa: E402


def unchanged_state(trainer):
    """The step computes its loss but hands back the state it was given."""
    def broken(fn):
        def step(state, batch):
            _, metrics = fn(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return step
    chipbench_cells.plant_step(trainer, broken)


@pytest.mark.parametrize("workload", ["smollm-135m.train"])
def test_control_is_not_correct(workload):
    cell = chipbench_cells.reduced_cell(workload)
    harness.init_jax(cell, chip=False)
    b1 = cell.mix["optimizer"]["beta1"]
    ref = harness.reference_readings(cell, 7)
    ctrl = harness.reference_readings(cell, 7, param_dtype=jnp.bfloat16)
    nums = compare.numbers(calibrate.as_program(ctrl, b1), ref, b1)
    correct, _, _ = compare.judge(nums, cell.limits)
    assert not correct, nums


def _run(cell, plant):
    return harness.run_cell(cell, seed=2**34 + 1, seconds=0.5, trace=False,
                            t_process=time.perf_counter(), reduced=True,
                            chip=False, plant=plant)


@pytest.mark.parametrize("workload", ["smollm-135m.train"])
def test_unchanged_state_is_not_correct(workload):
    out = _run(chipbench_cells.reduced_cell(workload), unchanged_state)
    assert out["correct"] is False
    assert out["checks"]["update_gap"]["value"] == pytest.approx(1.0, abs=1e-4)


@pytest.mark.parametrize("workload", ["smollm-135m.train"])
def test_half_batch_is_not_correct(workload):
    out = _run(chipbench_cells.reduced_cell(workload),
               chipbench_cells.plant_rows(lambda trainer: 0.5))
    assert out["correct"] is False, out["checks"]


NO_EXCHANGE = """
import json, sys, time
sys.path.insert(0, {here!r})
import chipbench_cells, harness
cell = chipbench_cells.reduced_cell("smollm-135m.elastic4")
out = harness.run_cell(
    cell, seed=2**34 + 1, seconds=0.5, trace=False,
    t_process=time.perf_counter(), reduced=True, chip=False,
    plant=chipbench_cells.plant_rows(lambda trainer: 1 / trainer.slices))
print(json.dumps(out))
"""


def test_no_exchange_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(chipbench_cells.ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", NO_EXCHANGE.format(here=HERE)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
