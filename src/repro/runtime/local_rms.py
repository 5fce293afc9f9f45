"""In-process RMS endpoint for live (non-simulated) elastic jobs.

Wraps the same :class:`~repro.rms.policy.ReconfigPolicy` the simulator uses,
over a real :class:`~repro.rms.cluster.Cluster`, with wall-clock timing —
this is what a single-controller deployment talks to (in a multi-controller
deployment the same protocol rides a gRPC/socket transport to the real
scheduler; the policy code is identical).
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.core.actions import Action, Decision
from repro.rms.cluster import Cluster
from repro.rms.job import Job, JobState
from repro.rms.policy import PolicyConfig, ReconfigPolicy
from repro.rms.scheduler import MAX_PRIORITY


class LocalRMS:
    """RMSProtocol implementation over an in-process cluster."""

    def __init__(self, num_nodes: int,
                 policy: PolicyConfig = PolicyConfig()):
        self.cluster = Cluster(num_nodes)
        self.policy = ReconfigPolicy(policy)
        self.jobs: List[Job] = []
        self._lock = threading.Lock()

    def _start(self, job: Job) -> None:
        self.cluster.allocate(job.job_id, job.requested_nodes)
        job.nodes = job.requested_nodes
        job.state = JobState.RUNNING
        job.start_time = time.monotonic()

    def submit(self, job: Job, start: bool = False) -> Job:
        with self._lock:
            self.jobs.append(job)
            if start:
                self._start(job)
        return job

    def start_pending(self) -> List[Job]:
        """Start the queued jobs that fit the free nodes, in queue order."""
        with self._lock:
            started = []
            for job in self.pending():
                if job.requested_nodes <= self.cluster.free_nodes:
                    self._start(job)
                    started.append(job)
            return started

    def finish(self, job_id: int) -> None:
        with self._lock:
            self.cluster.release(job_id)
            for j in self.jobs:
                if j.job_id == job_id:
                    j.state = JobState.COMPLETED

    def pending(self) -> List[Job]:
        return [j for j in self.jobs if j.state is JobState.PENDING]

    # -- RMSProtocol -------------------------------------------------------

    def request_reconfig(self, job_id: int, *, current: int, minimum: int,
                         maximum: int, factor: int,
                         preferred: Optional[int]) -> Decision:
        with self._lock:
            job = next(j for j in self.jobs if j.job_id == job_id)
            with TraceAnnotation("rms.decide", job=job_id, current=current):
                t0 = time.perf_counter()
                decision = self.policy.decide(
                    self.cluster, self.pending(), job, minimum=minimum,
                    maximum=maximum, factor=factor, preferred=preferred)
                elapsed = time.perf_counter() - t0
            if decision.action is not Action.NO_ACTION:
                self.cluster.resize(job_id, decision.new_slices)
                job.nodes = decision.new_slices
            if decision.boost_job_id is not None:
                for q in self.jobs:
                    if q.job_id == decision.boost_job_id:
                        q.priority_boost = MAX_PRIORITY
            import dataclasses
            return dataclasses.replace(decision, schedule_time_s=elapsed)

    def confirm_resize(self, job_id: int, decision: Decision,
                       timeout_s: float) -> Tuple[bool, float]:
        # Single-controller: the resize transaction in request_reconfig is
        # atomic, so the RJ is already running by construction.
        return True, 0.0


def scripted_rival(rms: LocalRMS, *, submit_at: int, finish_at: int,
                   log: Callable[[str], None] = lambda msg: None
                   ) -> Callable[[int], None]:
    """An ``on_step`` hook for :meth:`ElasticTrainer.train` that scripts the
    cluster around a malleable job holding all of ``rms``'s nodes.

    At step ``submit_at`` a rival job asking for half the nodes joins the
    queue, so the policy's wide optimization shrinks the job; once the
    rival has started on the freed nodes and reached step ``finish_at`` it
    finishes, and the next reconfiguration point expands the job back.
    """
    half = rms.cluster.num_nodes // 2
    if half < 1:
        raise ValueError("a rival needs a cluster of at least 2 nodes")
    rival = Job(job_id=1, app="rival", submit_time=0.0, work=1e9,
                min_nodes=half, max_nodes=half, preferred=None,
                requested_nodes=half)

    def on_step(step: int) -> None:
        if step == submit_at:
            rms.submit(rival)
            log(f"[step {step}] rival job queued (wants {half} nodes)")
        if step == finish_at and rival.state is JobState.RUNNING:
            rms.finish(rival.job_id)
            log(f"[step {step}] rival finished, {half} nodes free")
        for job in rms.start_pending():
            log(f"[step {step}] job {job.job_id} started on "
                f"{job.nodes} nodes")
    return on_step
