"""Compile each cell's train step for a described TPU v5e, without a chip.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_rehearsal.py \
      [--workload NAME ...]

For every cell of ``BENCHMARK.json`` (or the ones named) it builds the
trainer through ``repro.launch.train.build`` as the benchmark does, hands
it the chips of a described ``v5e:2x2`` topology (the first one for a
one-chip cell; the first four and the first two for an elastic cell's two
layouts), and compiles the train step for them from shapes alone.  It
prints, per layout, the compiled program's memory a device (arguments,
outputs, temporaries, the sum against the chip's 16 GB) and how many
all-reduce, reduce-scatter and all-gather operations it holds.  Nothing
runs: this says what the chip's compiler accepts and what it allocates,
not how fast anything is.

Beside each cell's step it compiles, for the first chip, the set-up's
change of the weights (``harness.delta_norm_fn``, which runs beside the
program's state) and the reference's gradient and update
(``references/train.py``), which run after the program's state is freed.
Their rows give, besides what each program holds, ``phase_bytes``: that
and the trees that live beside it (the two moments, beside the gradient),
and ``param_copy_bytes``, one float32 copy of the parameters, the unit of
the harness's budget.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def layouts(cell) -> list:
    if cell.elastic:
        return sorted({n for pair in cell.mix["setup"]["resizes"]
                       for n in pair}, reverse=True)
    return [cell.mix["slices"]]


def rehearse(cell, topo) -> list:
    import jax

    import harness
    from repro.core import make_mesh
    from repro.launch.train import build

    jax.config.update("jax_enable_compilation_cache", False)
    mix = cell.mix
    # one slice and no RMS: this host has one CPU device; the described
    # chips are handed to the trainer below
    trainer, _ = build(cell.config["program"]["arch"],
                       seq_len=mix["seq_len"],
                       global_batch=mix["global_batch"],
                       lr=mix["optimizer"]["lr"],
                       steps=mix["optimizer"]["total_steps"])
    harness.set_options(trainer, cell)
    harness.check_program(trainer, cell)
    out = []
    for slices in layouts(cell):
        devs = list(topo.devices[:slices])
        trainer.devices = devs
        mesh = make_mesh(slices, 1, devices=devs)
        trainer.mesh = mesh
        shard = trainer._state_shardings(mesh)
        abstract = jax.eval_shape(lambda: trainer._fresh_state(0))
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            abstract, shard)
        bs = trainer._batch_shardings(mesh)
        shape = (cell.mix["global_batch"], cell.mix["seq_len"])
        batch = {k: jax.ShapeDtypeStruct(shape, jax.numpy.int32,
                                         sharding=bs[k])
                 for k in ("tokens", "labels")}
        with mesh:
            compiled = trainer.step_fn(mesh).lower(state, batch).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        coll = {k: len(re.findall(rf"\b{k}(-start)?\(", text))
                for k in ("all-reduce", "reduce-scatter", "all-gather")}
        out.append({"workload": cell.name, "slices": slices,
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "total_bytes": harness.held_bytes(ma),
                    "collectives": coll})
    return out


def rehearse_harness(cell, device) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import harness
    import traffic
    from references import train as ref_train

    ref, cfg, mix = cell.reference, cell.config, cell.mix
    one = SingleDeviceSharding(device)

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    key = placed(jax.eval_shape(
        lambda: traffic.seed_key(0, traffic.WEIGHTS)))
    params = jax.eval_shape(lambda k: ref.init_params(k, cfg), key)
    copy = sum(4 * x.size for x in jax.tree.leaves(params))
    params = placed(params)
    prog = placed(jax.eval_shape(lambda k: ref.to_program(
        ref.init_params(k, cfg), cfg), key))
    tokens = jax.ShapeDtypeStruct((mix["global_batch"], mix["seq_len"]),
                                  jnp.int32, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    decay = [ref_train.decays(n, x.shape) for n, x in
             zip(ref_train.leaf_names(params), jax.tree.leaves(params))]
    parts = [
        ("setup.delta_norm", 0,
         harness.delta_norm_fn(cell).lower(prog, key)),
        ("reference.gradient", 2 * copy,
         ref_train.make_grad_fn(ref, cfg).lower(params, tokens, tokens,
                                                params)),
        ("reference.update", 0,
         ref_train.make_update(mix["optimizer"], decay).lower(
             params, params, params, params, scalar, scalar)),
    ]
    out = []
    for part, beside, lowered in parts:
        ma = lowered.compile().memory_analysis()
        held = harness.held_bytes(ma)
        out.append({"workload": cell.name, "part": part,
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "total_bytes": held, "phase_bytes": held + beside,
                    "param_copy_bytes": copy})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    from jax.experimental import topologies
    import harness

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workload or [w["name"]
                              for w in harness.benchmark()["workloads"]]
    for name in names:
        cell = harness.load_cell(name)
        for row in rehearse(cell, topo) + rehearse_harness(
                cell, topo.devices[0]):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
