"""QSCH: queueing policies, admission, preemption, requeue (§3.2)."""

import pytest

from repro.core import (ClusterState, Job, JobKind, JobState, QueuePolicy,
                        QuotaMode, PRIO_HIGH, PRIO_LOW)
from conftest import PhaseLog, make_qsch


def _job(uid, gpus=8, n_pods=1, prio=50, t=0.0, tenant="t0", dur=3600.0):
    return Job(uid=uid, tenant=tenant, gpu_type=0, n_pods=n_pods,
               gpus_per_pod=gpus, priority=prio, submit_time=t,
               duration=dur)


def fill_cluster(qsch, state, now=0.0, uid0=100):
    """Occupy every node with 16 single-node 8-GPU jobs."""
    for i in range(16):
        qsch.submit(_job(uid0 + i, gpus=8, t=now))
    res = qsch.cycle(state, now)
    assert len(res.scheduled) == 16
    return res


def test_strict_fifo_head_blocks_queue(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.STRICT_FIFO)
    fill_cluster(qsch, state)
    qsch.submit(_job(1, n_pods=4, gpus=8, t=10.0))   # cannot fit
    qsch.submit(_job(2, gpus=1, t=11.0))             # could fit, but FIFO
    res = qsch.cycle(state, 30.0)
    assert res.scheduled == []
    assert res.blocked_head is not None and res.blocked_head.uid == 1


def test_best_effort_bypasses_head(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.BEST_EFFORT_FIFO,
                     priority_preemption=False)
    for i in range(15):                      # leave one node free
        qsch.submit(_job(100 + i, gpus=8))
    qsch.cycle(state, 0.0)
    qsch.submit(_job(1, n_pods=4, gpus=8, t=10.0))   # blocked head
    qsch.submit(_job(2, gpus=8, t=11.0))             # fits the free node
    res = qsch.cycle(state, 30.0)
    assert [j.uid for j in res.scheduled] == [2]


def test_backfill_schedules_small_and_preempts_on_timeout(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.BACKFILL,
                     backfill_head_timeout=100.0)
    for i in range(15):
        qsch.submit(_job(100 + i, gpus=8))
    qsch.cycle(state, 0.0)
    qsch.submit(_job(1, n_pods=2, gpus=8, t=10.0))   # head needs 2 nodes
    qsch.submit(_job(2, gpus=8, t=11.0))             # backfill fodder
    res = qsch.cycle(state, 20.0)
    assert [j.uid for j in res.scheduled] == [2]
    assert res.scheduled[0].backfilled
    # before timeout: no preemption
    res = qsch.cycle(state, 60.0)
    assert res.preempted == []
    # one long-running job ends -> a node frees
    done = next(j for j in qsch.running.values() if j.uid == 100)
    qsch.on_complete(done, state, 110.0)
    # after timeout: the head preempts the backfilled job to get node 2
    res = qsch.cycle(state, 130.0)
    assert any(j.uid == 2 for j in res.preempted)
    assert any(j.uid == 1 for j in res.scheduled)
    # preempted job was requeued (§3.2.4)
    j2 = next(j for j in qsch.pending_jobs() if j.uid == 2)
    assert j2.requeue_count == 1 and j2.state is JobState.PENDING


class CycleLog(PhaseLog):
    """A recording observer with QSCH's cycle and decision hooks."""

    def cycle_begin(self, now):
        pass

    def cycle_end(self, result, ctx):
        pass

    def emit_bind(self, job, sched, ctx):
        pass

    def emit_reject(self, job, sched, ctx, reason):
        pass

    def emit_preempt(self, victim, ctx, source):
        pass


def test_backfill_cycle_enters_head_attempt_then_backfill_pass(topo):
    """With an observer attached, a Backfill cycle enters
    ``head-attempt`` (the head's try) and then ``backfill-pass`` (the
    jobs behind it), both inside ``qsch-cycle``; RSCH runs inside the
    one that places.  Placements are the same without the observer."""
    placed = {}
    for observed in (False, True):
        state = ClusterState.create(topo)
        qsch = make_qsch(topo, state, policy=QueuePolicy.BACKFILL)
        log = CycleLog()
        if observed:
            qsch.obs = qsch.rsch.obs = log
        for i in range(15):
            qsch.submit(_job(100 + i, gpus=8))
        qsch.cycle(state, 0.0)
        qsch.submit(_job(1, n_pods=2, gpus=8, t=10.0))   # blocked head
        qsch.submit(_job(2, gpus=8, t=11.0))             # backfilled
        log.events.clear()
        blocked = qsch.cycle(state, 20.0)
        assert blocked.blocked_head.uid == 1
        assert [j.uid for j in blocked.scheduled] == [2]
        if observed:
            cycle = log.interval("qsch-cycle")
            head = log.interval("head-attempt")
            rest = log.interval("backfill-pass")
            rsch = log.interval("rsch-schedule")
            assert cycle[0] < head[0] < head[1] < rest[0] < rest[1] < cycle[1]
            assert rest[0] < rsch[0] < rsch[1] < rest[1]
        for uid in (100, 101):
            done = next(j for j in qsch.running.values() if j.uid == uid)
            qsch.on_complete(done, state, 30.0)
        log.events.clear()
        freed = qsch.cycle(state, 40.0)
        assert [j.uid for j in freed.scheduled] == [1]
        if observed:
            head = log.interval("head-attempt")
            rsch = log.interval("rsch-schedule")
            assert head[0] < rsch[0] < rsch[1] < head[1]
        placed[observed] = [
            (j.uid, [(p.node, tuple(p.gpu_indices)) for p in j.placement.pods])
            for j in sorted(qsch.running.values(), key=lambda j: j.uid)]
    assert placed[True] == placed[False]


def test_priority_preemption(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.BACKFILL)
    for i in range(16):
        qsch.submit(_job(100 + i, gpus=8, prio=PRIO_LOW))
    qsch.cycle(state, 0.0)
    qsch.submit(_job(1, gpus=8, prio=PRIO_HIGH, t=10.0))
    res = qsch.cycle(state, 30.0)
    assert any(j.uid == 1 for j in res.scheduled)
    assert len(res.preempted) >= 1


def test_conservative_preemption_no_thrash(topo, state):
    """Preemption must not fire when it provably cannot help."""
    qsch = make_qsch(topo, state, policy=QueuePolicy.BACKFILL)
    for i in range(16):
        qsch.submit(_job(100 + i, gpus=8, prio=PRIO_LOW))
    qsch.cycle(state, 0.0)
    # 17 whole nodes can never fit in a 16-node cluster
    qsch.submit(_job(1, n_pods=17, gpus=8, prio=PRIO_HIGH, t=10.0))
    res = qsch.cycle(state, 30.0)
    assert res.preempted == []


def test_static_quota_gates_global_queue(topo, state):
    qsch = make_qsch(topo, state, quota={"t0": {0: 8}})
    qsch.submit(_job(1, gpus=8))
    qsch.submit(_job(2, gpus=8))           # over quota, stays in queue
    res = qsch.cycle(state, 0.0)
    assert [j.uid for j in res.scheduled] == [1]
    assert qsch.queue_depth() == 1
    qsch.on_complete(qsch.running[1], state, 100.0)
    res = qsch.cycle(state, 130.0)
    assert [j.uid for j in res.scheduled] == [2]


def test_quota_reclamation_preemption(topo, state):
    qsch = make_qsch(topo, state, quota={"a": {0: 64}, "b": {0: 64}},
                     mode=QuotaMode.SHARED)
    # tenant a borrows the whole cluster
    for i in range(16):
        qsch.submit(_job(100 + i, gpus=8, tenant="a"))
    qsch.cycle(state, 0.0)
    # owner b wants its quota back
    qsch.submit(_job(1, gpus=8, tenant="b", t=10.0))
    res = qsch.cycle(state, 30.0)
    assert any(j.uid == 1 for j in res.scheduled)
    assert len(res.preempted) >= 1
    assert all(j.tenant == "a" for j in res.preempted)


def test_ordering_priority_time_size(topo, state):
    qsch = make_qsch(topo, state)
    qsch.submit(_job(1, gpus=8, prio=10, t=0.0))
    qsch.submit(_job(2, gpus=4, prio=50, t=5.0))
    qsch.submit(_job(3, gpus=2, prio=50, t=5.0))
    order = [j.uid for j in qsch.pending_jobs()]
    assert order == [3, 2, 1]      # prio desc, then size asc tiebreak


def test_one_snapshot_take_per_cycle(topo, state):
    """§3.4.3: mid-cycle placements are mirrored onto the working
    snapshot as deltas; the cluster is snapshotted exactly once."""
    qsch = make_qsch(topo, state)
    takes = []
    orig = qsch.snapshotter.take
    qsch.snapshotter.take = lambda s: takes.append(1) or orig(s)
    for i in range(10):
        qsch.submit(_job(100 + i, gpus=8))
    res = qsch.cycle(state, 0.0)
    assert len(res.scheduled) == 10
    assert len(takes) == 1
    # later placements saw the earlier ones: 10 distinct nodes
    assert len({j.placement.pods[0].node for j in res.scheduled}) == 10


def test_snapshot_placement_delta_equals_retake(topo, state):
    from repro.core import FullSnapshotter, snapshots_equal
    from repro.core.rsch import RSCH, RSCHConfig

    rsch = RSCH(topo, RSCHConfig())
    snap = FullSnapshotter().take(state)
    job = _job(1, gpus=4, n_pods=3)
    placement = rsch.schedule(job, snap).placement
    state.allocate(job, placement)
    snap.apply_placement(placement)
    assert snapshots_equal(snap, FullSnapshotter().take(state))
    released = state.release(job.uid)
    snap.apply_release(released)
    assert snapshots_equal(snap, FullSnapshotter().take(state))
