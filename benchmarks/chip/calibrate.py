"""Readings that the limits of ``limits/<workload>.json`` are set from.

  python3 benchmarks/chip/calibrate.py --workload smollm-135m.train \
      --seeds 101,102,...  --control-seeds 101,102,103

In one process, on the chips the cell needs, with the trainer built once:

- ``program``: for every seed, the program's first steps exactly as a run
  of the cell drives them in its set-up, against the reference (the lower
  readings: no measured window is needed for them);
- ``control``: for each control seed, the reference itself held at the
  next lower precision, bfloat16 parameters, against the float32
  reference (the upper readings);
- ``fault.half_batch``: the reference with half of each batch left out
  and the mean taken over the rest;
- ``fault.no_exchange`` (cells over several slices): the reference with
  each step's gradient from the first slice's rows alone, as a step that
  leaves out the exchange between chips computes it on that chip.

A step that returns its state unchanged reads 1 on ``update_gap`` by the
measure's definition and is not run.  One JSON line per reading; the
last line sums them up per number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def as_program(readings: dict, beta1: float) -> dict:
    """A reference's readings in the form the program's are taken in."""
    return {"losses": readings["losses"],
            "mu": {n: v * (1.0 - beta1) for n, v in readings["grad"].items()},
            "delta": readings["delta"], "resizes": None}


def slices_per_step(cell) -> list:
    mix = cell.mix
    if not cell.elastic:
        return [mix["slices"]] * mix["setup_steps"]
    return [mix["slices"]] + [to for _, to in mix["setup"]["resizes"]]


def values(nums: dict) -> dict:
    return {k: v["value"] for k, v in nums.items()}


def control_and_faults(cell, seed: int, ref: dict):
    """(kind, numbers) of the control and the planted faults for a seed."""
    import jax.numpy as jnp
    import compare
    import harness

    b1 = cell.mix["optimizer"]["beta1"]
    batch = cell.mix["global_batch"]
    runs = [("control", {"param_dtype": jnp.bfloat16}),
            ("fault.half_batch", {"rows": lambda k: slice(0, batch // 2)})]
    per = slices_per_step(cell)
    if max(per) > 1:
        runs.append(("fault.no_exchange",
                     {"rows": lambda k: slice(0, batch // per[k])}))
    for kind, kw in runs:
        got = harness.reference_readings(cell, seed, **kw)
        yield kind, compare.numbers(as_program(got, b1), ref, b1)


def calibrate(cell, seeds, control_seeds, *, reduced=False, chip=True,
              emit=print):
    import jax
    import harness

    harness.init_jax(cell, chip)
    job = harness.start_job(cell, reduced)
    rows = []
    for seed in seeds:
        state, prog = harness.first_steps(job, cell, seed)
        mismatch = harness.reshard_mismatch(job.rec)
        del state
        ref = harness.reference_readings(cell, seed)
        nums = harness.numbers(cell, prog, ref, mismatch)
        rows.append({"kind": "program", "seed": seed, **values(nums)})
        emit(json.dumps(rows[-1]))
        if seed in control_seeds:
            for kind, n in control_and_faults(cell, seed, ref):
                rows.append({"kind": kind, "seed": seed, **values(n)})
                emit(json.dumps(rows[-1]))
        jax.clear_caches()
    summary = {}
    for r in rows:
        for k, v in r.items():
            if k in ("kind", "seed"):
                continue
            s = summary.setdefault(r["kind"], {}).setdefault(
                k, {"min": v, "max": v})
            s["min"], s["max"] = min(s["min"], v), max(s["max"], v)
    emit(json.dumps({"summary": summary}))
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    cell = harness.load_cell(args.workload)
    try:
        calibrate(cell, seeds, control)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
