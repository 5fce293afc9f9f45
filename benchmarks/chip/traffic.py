"""The job's inputs and the cluster's traffic, made from the seed and the mix.

- ``TokenFeed``: the training batches, ``global_batch`` rows of ``seq_len``
  next-token pairs a step, drawn uniformly from the vocabulary on the
  device in one jitted call per step.  Every row of every step differs, and
  every seed gets the same shapes.
- ``Rival``: the other job of the cluster, a periodic version of
  ``repro.runtime.local_rms.scripted_rival``: at each step of ``arrive`` a
  new rival job that wants ``nodes`` nodes is queued, so the policy's wide
  optimisation shrinks the malleable job at that reconfiguration point; at
  each step of ``leave`` the running rival finishes, so the job is expanded
  there again.
- ``plan_segments``: the window's segments, ended each by a wait for the
  state.  A resize at step r gets the short segment [r-1, r+1): the step
  before the reconfiguration point, the resize, and the first step on the
  new layout.  Steady stretches are cut into segments of at most
  ``segment_steps``.
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Set, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def seed_key(seed: int, salt: int):
    """A PRNG key from all bits of a seed that may pass 32 bits."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, salt)


WEIGHTS, TOKENS = 0, 1   # salts of the two streams a seed feeds


class TokenFeed:
    """``batch(step)`` as the trainer calls it.  The seed's key is an
    argument of the one compiled program, so every seed shares it."""

    def __init__(self, seed: int, vocab: int, seq_len: int,
                 global_batch: int):
        self.key = seed_key(seed, TOKENS)

        @jax.jit
        def make(key, step):
            k = jax.random.fold_in(key, step)
            t = jax.random.randint(k, (global_batch, seq_len + 1), 0, vocab,
                                   dtype=jnp.int32)
            return {"tokens": t[:, :-1], "labels": t[:, 1:]}
        self.make = make

    def at(self, step: int):
        return self.make(self.key, step)

    def batch(self, step: int):
        with TraceAnnotation("data.batch"):
            return self.at(step)


class Rival:
    """``on_step`` hook for ``ElasticTrainer.train``."""

    def __init__(self, rms, job_cls, nodes: int):
        self.rms = rms
        self.job_cls = job_cls
        self.nodes = nodes
        self.arrive: Set[int] = set()
        self.leave: Set[int] = set()
        self.running = None
        self.next_id = 1

    def plan(self, arrive: Iterable[int], leave: Iterable[int]) -> None:
        self.arrive, self.leave = set(arrive), set(leave)

    def __call__(self, step: int) -> None:
        for job in self.rms.start_pending():
            self.running = job
        if step in self.leave and self.running is not None:
            self.rms.finish(self.running.job_id)
            self.running = None
        if step in self.arrive:
            self.rms.submit(self.job_cls(
                job_id=self.next_id, app="rival", submit_time=0.0, work=1e9,
                min_nodes=self.nodes, max_nodes=self.nodes, preferred=None,
                requested_nodes=self.nodes))
            self.next_id += 1


def cycle_events(first: int, check_period: int, wide: int, narrow: int,
                 cycles: int) -> Tuple[List[int], List[int]]:
    """Arrivals and departures of ``cycles`` rival visits: the job runs
    ``wide`` steps on all its slices from the first reconfiguration point
    after ``first``, then ``narrow`` shrunk, and so on."""
    if wide % check_period or narrow % check_period:
        raise ValueError("the rival's phases must be whole check periods")
    c0 = (first // check_period + 1) * check_period
    arrive = [c0 + wide + k * (wide + narrow) for k in range(cycles)]
    return arrive, [a + narrow for a in arrive]


def plan_segments(start: int, stop: int, resizes: Iterable[int],
                  segment_steps: int) -> Iterator[Tuple[int, int, bool]]:
    """``(first, end, holds_resize)`` for steps [start, stop), in order."""
    at = start
    for r in sorted(resizes):
        if not start < r - 1 < stop - 1:
            continue
        while at < r - 1:
            end = min(at + segment_steps, r - 1)
            yield at, end, False
            at = end
        yield r - 1, r + 1, True
        at = r + 1
    while at < stop:
        end = min(at + segment_steps, stop)
        yield at, end, False
        at = end
