"""Elastic resharding of program state across meshes.

This is the JAX analogue of the paper's §5.2 reconfiguration mechanics: after
the RMS grants an expand/shrink, the job's *entire state* (parameters,
optimizer moments, recurrent/KV state, RNG, step counter) must continue on a
mesh with a different number of data-parallel slices.

Two paths are provided, mirroring the paper's discussion:

- :func:`reshard` — *runtime data redistribution* (the paper's contribution):
  the state moves chip to chip.  ``jax.device_put`` alone copies a leaf
  between devices only where every new shard's index box is held by some old
  shard; otherwise it pulls the whole array to host memory and places it
  again.  A ZeRO-1 moment split in quarters on 4 slices and in halves on 2 is
  such a leaf.  :func:`plan_reshard` routes those leaves through a *bridge*:
  the smaller layout's spec on the larger of the two nested device sets.  A
  jitted relayout moves a leaf between the larger layout and the bridge on
  the larger device set (XLA's collectives), and ``jax.device_put`` between
  the bridge and the smaller layout, whose every box the bridge holds.  The
  new layout's ownership is the Listing-3 mapping (checked in
  ``tests/test_multidevice.py``).
- :func:`checkpoint_reshard` — the *checkpoint-and-reconfigure* baseline the
  paper improves on ([6] in the paper): state is pulled to host memory and
  re-placed onto the new mesh.  Slower (host round-trip) but survives device
  loss — this is also the node-failure recovery path.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation
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


@dataclasses.dataclass(frozen=True)
class ReshardPlan:
    """How :func:`reshard` moves a state onto new shardings, per flattened
    leaf.  A function of the two layouts: make it once a pair."""

    # the bridge sharding a leaf passes through, or None: one device_put
    bridges: Tuple[Optional[NamedSharding], ...]
    # per leaf: the jitted relayout runs before the device_put (onto fewer
    # devices) rather than after it (onto more)
    shrinks: Tuple[bool, ...]
    # leaves whose device_put finds some new shard's box on no device, and
    # so crosses host memory
    host_leaves: int
    note: str = ""       # why leaves cross host memory, if any do


def crosses_host(src: NamedSharding, dst: NamedSharding, shape) -> bool:
    """Whether ``jax.device_put`` of an array laid out as ``src`` onto
    ``dst`` goes through host memory: the device sets differ, the source
    spans more than one device, and some new shard's index box is held by no
    old shard."""
    if src.device_set == dst.device_set or len(src.device_set) == 1:
        return False
    shape = tuple(shape)
    held = {tuple(_box(idx, shape))
            for idx in src.devices_indices_map(shape).values()}
    return any(tuple(_box(idx, shape)) not in held
               for idx in dst.devices_indices_map(shape).values())


def plan_reshard(state: Any, shardings: Any) -> ReshardPlan:
    """Bridge every leaf that a plain ``jax.device_put`` would send through
    host memory, where the two meshes nest on a device prefix
    (:func:`repro.core.meshes.make_mesh`); the others take one
    ``device_put``."""
    leaves = jax.tree.leaves(state)
    targets = jax.tree.leaves(shardings)
    bridges, shrinks, host = [], [], 0
    for x, dst in zip(leaves, targets):
        src = x.sharding
        shrink = len(dst.device_set) < len(src.device_set)
        bridge = _bridge(src, dst) if crosses_host(src, dst, x.shape) \
            else None
        put = (src, dst) if bridge is None else \
            (bridge, dst) if shrink else (src, bridge)
        host += crosses_host(*put, x.shape)
        bridges.append(bridge)
        shrinks.append(shrink)
    note = f"{host} leaves cross host memory: their meshes do not nest" \
        if host else ""
    return ReshardPlan(tuple(bridges), tuple(shrinks), host, note)


def reshard(state: Any, shardings: Any, *, plan: Optional[ReshardPlan] = None,
            span: Optional[Mapping[str, Any]] = None,
            donate: bool = True) -> Any:
    """Runtime redistribution: move ``state`` onto ``shardings``.

    ``shardings`` is a pytree of NamedSharding matching ``state``; ``plan``
    is :func:`plan_reshard` of the two (made here if not given), and
    ``span`` labels the ``reshard.relayout`` trace span around the dispatch
    of the jitted relayouts.  Every leaf lands on its sharding bit for bit.
    ``donate`` is accepted and ignored: the old buffers stay alive until the
    caller drops them, so a resize holds both layouts at once.
    """
    del donate
    if plan is None:
        plan = plan_reshard(state, shardings)
    leaves, tree = jax.tree.flatten(state)
    targets = jax.tree.leaves(shardings)
    before = [i for i, b in enumerate(plan.bridges)
              if b is not None and plan.shrinks[i]]
    after = [i for i, b in enumerate(plan.bridges)
             if b is not None and not plan.shrinks[i]]
    span = dict(span or {})
    if before:
        with TraceAnnotation("reshard.relayout", **span):
            moved = _relayout(tuple(plan.bridges[i] for i in before))(
                *[leaves[i] for i in before])
        for i, x in zip(before, moved):
            leaves[i] = x
    placed = list(targets)
    for i in after:
        placed[i] = plan.bridges[i]
    leaves = jax.device_put(leaves, placed)
    if after:
        with TraceAnnotation("reshard.relayout", **span):
            moved = _relayout(tuple(targets[i] for i in after))(
                *[leaves[i] for i in after])
        for i, x in zip(after, moved):
            leaves[i] = x
    return jax.tree.unflatten(tree, leaves)


def _bridge(src: NamedSharding, dst: NamedSharding) -> \
        Optional[NamedSharding]:
    """The smaller layout's spec on the larger layout's devices, in their
    order, under a leading ``bridge`` axis: device ``i`` holds the smaller
    layout's shard ``i mod small``.  None where the meshes do not nest."""
    if not (isinstance(src, NamedSharding) and isinstance(dst, NamedSharding)):
        return None
    big, small = (src, dst) if len(src.device_set) > len(dst.device_set) \
        else (dst, src)
    n, k = small.mesh.devices.size, big.mesh.devices.size
    if k % n or list(big.mesh.devices.flat[:n]) != \
            list(small.mesh.devices.flat):
        return None
    mesh = Mesh(big.mesh.devices.reshape((k // n,) + small.mesh.devices.shape),
                ("bridge",) + tuple(small.mesh.axis_names))
    return NamedSharding(mesh, small.spec)


@functools.lru_cache(maxsize=None)
def _relayout(shardings: tuple):
    """One jitted relayout onto ``shardings``, compiled once for each input
    layout and kept for the next resize between the same pair."""
    def relayout(*xs):
        return xs
    return jax.jit(relayout, out_shardings=shardings)


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
