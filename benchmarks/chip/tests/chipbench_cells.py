"""The benchmark's cells at the program's reduced sizes, for CPU tests.

Each cell is loaded from the benchmark's own files by name and then cut to
what ``repro.models.reduced_config`` builds (``build(..., reduced=True)``):
the configuration's sizes and the program values the harness checks, as its
file's ``cpu_test`` key gives them, and the mix's sequence length and
batch.  Nothing else changes.
"""
from __future__ import annotations

import copy
import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def configs() -> list:
    """The name of every configuration file under ``configs/``."""
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, "configs")) if f.endswith(".json"))


def reduced_cell(name: str, seq_len: int = 32, batch: int = 4
                 ) -> "harness.Cell":
    return reduce(harness.load_cell(name), seq_len, batch)


def config_cell(config: str, traffic: str) -> "harness.Cell":
    """A one-chip cell of a configuration and a traffic mix that
    ``BENCHMARK.json`` does not pair, with no limits."""
    conf = harness.load_json(f"configs/{config}.json")
    return harness.Cell(name=f"{config}.{traffic}", chips=1, config=conf,
                        mix=harness.load_json(f"mixes/{traffic}.json"),
                        limits={},
                        reference=harness.load_module(conf["reference"]),
                        per_layer=[])


def reduce(cell: "harness.Cell", seq_len: int = 32, batch: int = 4
           ) -> "harness.Cell":
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config),
                               mix=copy.deepcopy(cell.mix))
    cut = cell.config["cpu_test"]
    cell.config.update(cut["sizes"])
    cell.config["program"]["values"].update(cut["values"])
    cell.mix.update(seq_len=seq_len, global_batch=batch)
    if cell.elastic:
        cell.mix.update(check_period=2, segment_steps=4,
                        rival={"nodes": 2, "wide_steps": 4,
                               "narrow_steps": 4})
    else:
        cell.mix.update(segment_steps=3)
    return cell


def plant_step(trainer, broken):
    """Put ``broken(fn)`` in place of each compiled step ``fn`` the trainer
    hands out: a fault under the harness, in the timed path."""
    step_fn, made = trainer.step_fn, {}

    def planted(mesh):
        fn = step_fn(mesh)
        if fn not in made:
            made[fn] = broken(fn)
            made[fn].lower = fn.lower
        return made[fn]
    trainer.step_fn = planted


def plant_rows(frac):
    """A fault: each step trains on the first ``frac(trainer)`` of its
    batch's rows, repeated to the batch's size, so that the loss and the
    gradient are the mean over those rows alone."""
    import jax.numpy as jnp

    def plant(trainer):
        def broken(fn):
            def step(state, batch):
                b = batch["tokens"].shape[0]
                n = max(1, round(b * frac(trainer)))
                return fn(state, {k: jnp.tile(v[:n], (b // n, 1))
                                  for k, v in batch.items()})
            return step
        plant_step(trainer, broken)
    return plant
