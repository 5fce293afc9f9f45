"""Factor-based data redistribution plans & collectives (Listing 3 / Fig. 2).

The paper's programming model redistributes data homogeneously: an *expand*
by factor ``f`` splits each of the ``P`` old ranks' data into ``f`` chunks,
chunk ``i`` of old rank ``r`` going to new rank ``r*f + i`` (Fig. 2a); a
*shrink* by factor ``f`` groups ranks in blocks of ``f``, the last member of
each block (the *receiver*) collecting the other ``f-1`` *senders'* data
(Fig. 2b) and continuing as new rank ``r // f``.

Three artefacts live here:

- :func:`expand_plan` / :func:`shrink_plan` — explicit transfer plans
  (src slice, dst slice, bytes).  These drive the simulator's
  redistribution cost model and are validated against what
  ``jax.device_put`` actually does.
- :func:`transfer_time_s` — the Fig.-3 cost model: concurrent transfers over
  per-slice links, plus the shrink synchronization term.
- :func:`migrate_slice` — an in-mesh ``shard_map``/``ppermute`` migration of
  one slice's shard to another slice (used for straggler mitigation, where
  the slice *count* is unchanged but membership rotates).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Transfer:
    src: int          # old-configuration slice id
    dst: int          # new-configuration slice id
    nbytes: int
    local: bool       # True when src slice maps onto the same devices


def _check_factor(p: int, q: int) -> int:
    big, small = max(p, q), min(p, q)
    if small <= 0 or big % small:
        raise ValueError(f"sizes {p}->{q} are not multiple/divisor related")
    return big // small


def expand_plan(p: int, q: int, nbytes: int) -> List[Transfer]:
    """P -> Q = P*f slices. Old rank r keeps chunk 0 locally (original nodes
    are reused, §5.2.1) and sends chunks 1..f-1 out."""
    f = _check_factor(p, q)
    if q < p:
        raise ValueError("expand requires q > p")
    chunk = nbytes // q  # bytes per new slice (global nbytes)
    plan = []
    for r in range(p):
        for i in range(f):
            dst = r * f + i
            plan.append(Transfer(src=r, dst=dst, nbytes=chunk,
                                 local=(i == 0)))
    return plan


def shrink_plan(p: int, q: int, nbytes: int) -> List[Transfer]:
    """P -> Q = P/f slices. Receivers are ranks with r % f == f-1
    (Listing 3: ``sender = (rank % f) < f-1``); receiver r continues as new
    rank r // f."""
    f = _check_factor(p, q)
    if q > p:
        raise ValueError("shrink requires q < p")
    chunk = nbytes // p  # bytes per old slice
    plan = []
    for r in range(p):
        receiver = f * (r // f + 1) - 1           # Listing 3 line 19
        new_rank = r // f
        plan.append(Transfer(src=r, dst=new_rank, nbytes=chunk,
                             local=(r == receiver)))
    return plan


# -- Fig. 3 cost model -------------------------------------------------------

def plan_stats(plan: List[Transfer]) -> Tuple[int, int]:
    """``(participants, busiest_link_bytes)`` of a transfer plan.

    These are the two features the Fig.-3 cost model (and the calibration
    fitter in :mod:`repro.calib.fit`) is linear in: the busiest per-slice
    link bounds the transfer, the participant count drives the shrink
    synchronization barrier.
    """
    send = {}
    recv = {}
    participants = set()
    for t in plan:
        participants.add(t.src)
        participants.add(t.dst)
        if t.local:
            continue
        send[t.src] = send.get(t.src, 0) + t.nbytes
        recv[t.dst] = recv.get(t.dst, 0) + t.nbytes
    busiest = max([*send.values(), *recv.values(), 0])
    return len(participants), busiest


def transfer_time_s(plan: List[Transfer], *, link_bw: float,
                    latency_s: float = 0.0,
                    sync_s_per_participant: float = 0.0) -> float:
    """Completion time of a redistribution plan.

    Each slice sends/receives over its own link at ``link_bw`` B/s; the plan
    completes when the busiest link drains.  ``sync_s_per_participant``
    models the shrink barrier (ACK collection at the management node,
    §5.2.2) — the paper observes shrinks cost more synchronization the
    larger the participant-count gap.
    """
    participants, busiest = plan_stats(plan)
    return latency_s + busiest / link_bw + \
        sync_s_per_participant * participants


# -- In-mesh slice migration (straggler path) -------------------------------

def migrate_slice(x: jax.Array, mesh: Mesh, src: int, dst: int,
                  axis: str = "data") -> jax.Array:
    """Swap the shards held by slices ``src`` and ``dst`` along ``axis``.

    Used when the RMS reshapes a job away from a straggling slice: data
    moves, the logical layout (sharding) is unchanged.  Implemented as a
    ``ppermute`` inside ``shard_map`` so the collective schedule is explicit
    (one bidirectional ICI exchange).
    """
    n = mesh.shape[axis]
    perm = []
    for i in range(n):
        j = dst if i == src else (src if i == dst else i)
        perm.append((i, j))

    spec = P(axis)
    other_axes = tuple(a for a in mesh.axis_names if a != axis)

    def body(blk):
        return jax.lax.ppermute(blk, axis, perm)

    fn = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    # Collapse other mesh axes by treating them as replicated for this op.
    del other_axes
    return fn(x)
