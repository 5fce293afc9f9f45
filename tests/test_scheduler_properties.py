"""Property-based scheduler invariants (policy registry, EASY backfill)."""
import random

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # container has no hypothesis
    from _hypothesis_stub import given, settings, strategies as st

import pytest

from repro.rms import (POLICY_REGISTRY, Cluster, Job, JobState, Scheduler,
                       SchedulerConfig)


def make_jobs(sizes, submit_times=None, state=JobState.PENDING):
    submit_times = submit_times or [float(i) for i in range(len(sizes))]
    jobs = []
    for i, (n, t) in enumerate(zip(sizes, submit_times)):
        j = Job(job_id=i, app="cg", submit_time=t, work=100.0,
                min_nodes=1, max_nodes=n, preferred=None,
                requested_nodes=n)
        j.state = state
        if state is JobState.RUNNING:
            j.nodes = n
        jobs.append(j)
    return jobs


def occupy(cluster, running):
    for j in running:
        cluster.allocate(j.job_id + 1000, j.nodes)


def rand_case(seed, num_nodes=32):
    """Deterministic random queue + running mix from a seed."""
    rng = random.Random(seed)
    n_run = rng.randint(0, 4)
    run_sizes = [rng.choice([1, 2, 4, 8]) for _ in range(n_run)]
    while sum(run_sizes) > num_nodes:
        run_sizes.pop()
    n_pend = rng.randint(1, 8)
    pend_sizes = [rng.choice([1, 2, 4, 8, 16, 32]) for _ in range(n_pend)]
    running = make_jobs(run_sizes, state=JobState.RUNNING)
    for i, j in enumerate(running):
        j.job_id += 100
    pending = make_jobs(pend_sizes,
                        [float(rng.randint(0, 50)) for _ in pend_sizes])
    estimates = {j.job_id: float(rng.randint(10, 500))
                 for j in running + pending}
    return num_nodes, running, pending, estimates


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(sorted(POLICY_REGISTRY)))
def test_starts_never_exceed_free_nodes(seed, policy):
    num_nodes, running, pending, est = rand_case(seed)
    cluster = Cluster(num_nodes)
    occupy(cluster, running)
    sched = Scheduler(cluster, SchedulerConfig(policy=policy))
    starts = sched.schedule(pending, running, now=60.0,
                            runtime_estimate=lambda j: est[j.job_id])
    # the preempt policy may free victim nodes before the starts apply
    freed = sum(v.nodes - max(new, 0)
                for v, new in sched.pop_preemptions())
    assert sum(n for _, n in starts) <= cluster.free_nodes + freed
    assert cluster.free_nodes + cluster.allocated_nodes == num_nodes


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(sorted(POLICY_REGISTRY)))
def test_starts_are_pending_and_unique(seed, policy):
    num_nodes, running, pending, est = rand_case(seed)
    cluster = Cluster(num_nodes)
    occupy(cluster, running)
    sched = Scheduler(cluster, SchedulerConfig(policy=policy))
    starts = sched.schedule(pending, running, now=60.0,
                            runtime_estimate=lambda j: est[j.job_id])
    ids = [j.job_id for j, _ in starts]
    assert len(ids) == len(set(ids))
    pend_ids = {j.job_id for j in pending}
    assert all(i in pend_ids for i in ids)
    for j, n in starts:
        if policy == "moldable":
            # start-size optimizer: any size within the job's range
            assert max(j.min_nodes, 1) <= n <= j.max_nodes
        else:
            assert n == j.requested_nodes


def head_reservation_time(free, head_need, releases):
    """Earliest t where `head_need` nodes are available."""
    avail = free
    if avail >= head_need:
        return 0.0
    for t, n in sorted(releases):
        avail += n
        if avail >= head_need:
            return t
    return float("inf")


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_easy_backfill_never_delays_head_reservation(seed):
    """Backfilled jobs must leave the blocked head startable no later than
    its reservation computed before backfilling."""
    check_backfill_keeps_head_reservation(seed)


@pytest.mark.parametrize("seed", [151, 3532])
def test_easy_reservation_counts_jobs_started_in_the_same_pass(seed):
    """Cases where jobs start ahead of the blocked head: their releases
    must count towards the head's reservation, or a backfill that ends
    after it is admitted and delays the head."""
    check_backfill_keeps_head_reservation(seed)


def check_backfill_keeps_head_reservation(seed):
    num_nodes, running, pending, est = rand_case(seed)
    cluster = Cluster(num_nodes)
    occupy(cluster, running)
    now = 60.0
    sched = Scheduler(cluster, SchedulerConfig(policy="easy"))
    order = sched.order(pending, now)
    starts = sched.schedule(pending, running, now,
                            runtime_estimate=lambda j: est[j.job_id])
    started = {j.job_id for j, _ in starts}
    blocked = [j for j in order if j.job_id not in started]
    if not blocked:
        return
    head = blocked[0]
    head_pos = [j.job_id for j in order].index(head.job_id)
    prefix = [(j, n) for j, n in starts
              if [x.job_id for x in order].index(j.job_id) < head_pos]
    backfills = [(j, n) for j, n in starts if (j, n) not in prefix]
    # Reservation as seen when the head blocked: prefix starts consumed.
    free_at_head = cluster.free_nodes - sum(n for _, n in prefix)
    releases0 = [(now + est[j.job_id], j.nodes) for j in running] + \
                [(now + est[j.job_id], n) for j, n in prefix]
    t_resv = head_reservation_time(free_at_head, head.requested_nodes,
                                   releases0)
    # After backfilling: less free now, but backfills also release later.
    free1 = free_at_head - sum(n for _, n in backfills)
    releases1 = releases0 + [(now + est[j.job_id], n) for j, n in backfills]
    t_after = head_reservation_time(free1, head.requested_nodes, releases1)
    assert t_after <= t_resv + 1e-9


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_priority_order_is_total_and_stable_under_ties(seed):
    rng = random.Random(seed)
    num_nodes = 64
    cluster = Cluster(num_nodes)
    sched = Scheduler(cluster, SchedulerConfig())
    # Several jobs share (size, submit) => identical priority; job_id breaks
    # the tie, so any input permutation must produce the same order.
    sizes = [rng.choice([4, 8]) for _ in range(10)]
    submits = [float(rng.choice([0, 10])) for _ in range(10)]
    jobs = make_jobs(sizes, submits)
    now = 100.0
    ref = sched.order(list(jobs), now)
    for _ in range(5):
        shuffled = list(jobs)
        rng.shuffle(shuffled)
        assert [j.job_id for j in sched.order(shuffled, now)] == \
            [j.job_id for j in ref]
    # total order: strictly sorted by the sort key
    keys = [(-sched.priority(j, now), j.submit_time, j.job_id) for j in ref]
    assert keys == sorted(keys)
    assert len({j.job_id for j in ref}) == len(ref)


def test_boost_dominates_priority():
    cluster = Cluster(64)
    sched = Scheduler(cluster, SchedulerConfig())
    jobs = make_jobs([4, 4], [0.0, 1000.0])
    jobs[1].priority_boost = 1e12
    order = sched.order(jobs, now=2000.0)
    assert order[0].job_id == 1


def test_unknown_policy_raises():
    with pytest.raises(ValueError):
        Scheduler(Cluster(8), SchedulerConfig(policy="nope"))


def test_fcfs_blocks_behind_head():
    """FCFS: a job that fits must NOT start if a higher-priority job is
    blocked ahead of it."""
    cluster = Cluster(8)
    # Head needs 16 (> 8): nothing behind it may start under fcfs.  The
    # head's age dwarfs the small job's size bonus, so it tops the queue.
    jobs = make_jobs([16, 2], [0.0, 9_900.0])
    jobs[0].requested_nodes = 16
    sched = Scheduler(cluster, SchedulerConfig(policy="fcfs"))
    starts = sched.schedule(jobs, [], now=10_000.0,
                            runtime_estimate=lambda j: 100.0)
    assert starts == []
    easy = Scheduler(cluster, SchedulerConfig(policy="easy"))
    starts = easy.schedule(jobs, [], now=10_000.0,
                           runtime_estimate=lambda j: 100.0)
    assert [j.job_id for j, _ in starts] == [1]   # EASY backfills it


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000))
def test_conservative_backfill_false_is_fcfs(seed):
    """``SchedulerConfig.backfill=False`` must be honored by conservative
    (regression: it used to be silently ignored): without backfill no job
    may start ahead of a blocked higher-priority job — fcfs semantics."""
    num_nodes, running, pending, est = rand_case(seed)

    def starts_for(policy, backfill=True):
        cluster = Cluster(num_nodes)
        occupy(cluster, running)
        sched = Scheduler(cluster, SchedulerConfig(policy=policy,
                                                   backfill=backfill))
        return sched.schedule(pending, running, now=60.0,
                              runtime_estimate=lambda j: est[j.job_id])

    cons = starts_for("conservative", backfill=False)
    fcfs = starts_for("fcfs")
    assert [(j.job_id, n) for j, n in cons] == \
        [(j.job_id, n) for j, n in fcfs]


def test_conservative_backfill_false_blocks_behind_head():
    """Pin the honored behavior on the fcfs blocking scenario."""
    cluster = Cluster(8)
    jobs = make_jobs([16, 2], [0.0, 9_900.0])
    jobs[0].requested_nodes = 16
    sched = Scheduler(cluster, SchedulerConfig(policy="conservative",
                                               backfill=False))
    starts = sched.schedule(jobs, [], now=10_000.0,
                            runtime_estimate=lambda j: 100.0)
    assert starts == []                  # head blocks; nothing leapfrogs
    with_bf = Scheduler(cluster, SchedulerConfig(policy="conservative"))
    starts = with_bf.schedule(jobs, [], now=10_000.0,
                              runtime_estimate=lambda j: 100.0)
    assert [j.job_id for j, _ in starts] == [1]   # backfill reserves + fills


def test_conservative_skips_job_that_can_never_fit():
    """A request larger than the cluster gets no reservation and must not
    be started (regression: the fallback used to over-allocate)."""
    cluster = Cluster(4)
    jobs = make_jobs([8, 2], [0.0, 1.0])
    jobs[0].requested_nodes = 8
    sched = Scheduler(cluster, SchedulerConfig(policy="conservative"))
    starts = sched.schedule(jobs, [], now=10.0,
                            runtime_estimate=lambda j: 100.0)
    assert [j.job_id for j, _ in starts] == [1]
    assert all(n <= 4 for _, n in starts)


def test_malleable_releases_conserve_held_nodes():
    """The shrinkable split must not double-count a job's nodes
    (regression: phantom release was added on top of the full one)."""
    cluster = Cluster(64)
    runner = make_jobs([32], state=JobState.RUNNING)[0]
    runner.malleable = True
    runner.min_nodes = 4
    runner.check_period_s = 15.0
    cluster.allocate(runner.job_id, 32)
    pol = Scheduler(cluster, SchedulerConfig(policy="malleable")).policy
    releases = pol._releases([runner], 0.0, lambda j: 1000.0)
    assert sum(n for _, n in releases) == 32
    assert releases == [(15.0, 16), (1000.0, 16)]


def test_malleable_policy_reserves_earlier():
    """A malleable running job's shrinkable nodes count as an early release,
    so the malleable policy can refuse a long backfill that EASY accepts."""
    cluster = Cluster(16)
    runner = make_jobs([16], state=JobState.RUNNING)[0]
    runner.job_id = 99
    runner.malleable = True
    runner.min_nodes = 4
    runner.check_period_s = 15.0
    cluster.allocate(runner.job_id, 16)
    # Head needs 8; a long 4-node job could backfill under plain EASY
    # (reservation at runner's end) but would delay the earlier
    # malleability-aware reservation.
    # Head is much older than the filler so it tops the priority order.
    head = make_jobs([8], [0.0])[0]
    filler = make_jobs([4], [95.0])[0]
    filler.job_id = 1
    est = {99: 1000.0, 0: 500.0, 1: 900.0}
    easy = Scheduler(cluster, SchedulerConfig(policy="easy"))
    mall = Scheduler(cluster, SchedulerConfig(policy="malleable"))
    # no free nodes at all => neither starts anything; free 4 nodes first
    cluster.resize(99, 12)
    runner.nodes = 12
    est_fn = lambda j: est[j.job_id]
    s_easy = easy.schedule([head, filler], [runner], 100.0, est_fn)
    s_mall = mall.schedule([head, filler], [runner], 100.0, est_fn)
    assert [j.job_id for j, _ in s_easy] == [1]
    assert s_mall == []   # spare nodes held back for the sooner reservation
