"""Million-node scheduling core: SoA columns, vectorized top-k slot
engine, tracked group aggregates, subset scoring, cycle pipelining."""

import numpy as np
import pytest

from repro.core import (ClusterState, Job, JobKind, QSCH, QSCHConfig,
                        QueuePolicy, QuotaManager, RSCH, RSCHConfig,
                        Strategy)
from repro.core.framework import HealthFilter
from repro.core.scoring import (NEG_INF, _heap_slots, _prefilter_np,
                                chains_nondecreasing, pod_slots_np,
                                select_gang_slots)
from repro.core.simulator import SimConfig, Simulator
from repro.core.snapshot import FullSnapshotter
from repro.core.topology import small_topology
from conftest import make_qsch
from oracles import SequentialRSCH


# ----------------------------------------------------------------------
# Column layout (satellite: int32 pinning)
# ----------------------------------------------------------------------
def test_columns_are_int32_pinned(topo):
    state = ClusterState.create(topo)
    state.ensure_derived()
    cols = state.cols
    assert cols.gpu_type.dtype == np.int32
    assert cols.free_gpus.dtype == np.int32
    assert cols.used_gpus.dtype == np.int32
    assert cols.busy_count.dtype == np.int32
    assert cols.healthy_count.dtype == np.int32
    for b in (cols.gpu_busy, cols.gpu_healthy, cols.node_healthy,
              cols.inference_zone, cols.node_draining, cols.fragmented):
        assert b.dtype == np.bool_
    # Snapshots share the exact same block layout.
    snap = FullSnapshotter().take(state)
    assert snap.free_gpus.dtype == np.int32
    assert snap.cols.healthy_count.dtype == np.int32


def test_derived_columns_survive_direct_setup_writes(topo):
    """Tests/benches write state.gpu_busy directly before first use;
    the lazy derived init plus FullSnapshotter's re-derive must fold
    those writes in."""
    state = ClusterState.create(topo)
    state.gpu_busy[3, :5] = True
    assert int(state.free_gpus()[3]) == 3
    state.gpu_busy[4, :2] = True            # after derived init
    snap = FullSnapshotter().take(state)
    assert int(snap.free_gpus[4]) == 6
    assert bool(snap.cols.fragmented[4])


# ----------------------------------------------------------------------
# Vectorized top-k slot selection == heap oracle
# ----------------------------------------------------------------------
def _heap(scores, free, request, n_pods, fit_weight=0.0,
          colocate_bonus=0.0):
    """The heap walk over the same slots, or None where too few exist."""
    slots = pod_slots_np(free, scores, request)
    if int(slots.sum()) < n_pods:
        return None
    return _heap_slots(scores, free, slots, request, n_pods, fit_weight,
                       colocate_bonus)


def _random_case(rng, engineable=True):
    n = int(rng.integers(1, 200))
    free = rng.integers(0, 9, size=n).astype(np.int64)
    request = int(rng.choice([1, 2, 4, 8]))
    scores = np.where(
        (free >= request) & (rng.random(n) < 0.9),
        rng.choice([-2.0, -1.0, 0.0, 0.5, 1.0, 1.5],
                   size=n).astype(np.float32),
        np.float32(NEG_INF)).astype(np.float32)
    n_pods = int(rng.integers(1, 65))
    if engineable:
        colocate = float(rng.choice([0.0, 0.5, 2.0]))
        fit = float(rng.choice([0.0, 0.5, -0.25]))
        if not chains_nondecreasing(fit, colocate):
            fit = 0.5
    else:
        colocate, fit = -1.0, -0.5          # decreasing chains
    return scores, free, request, n_pods, fit, colocate


def test_topk_engine_matches_heap_fuzz():
    rng = np.random.default_rng(0)
    for _ in range(400):
        scores, free, request, n_pods, fit, colo = _random_case(rng)
        heap = _heap(scores, free, request, n_pods, fit, colo)
        topk = select_gang_slots(scores, free, request, n_pods,
                                 fit_weight=fit, colocate_bonus=colo)
        assert heap == topk


def test_topk_engine_edge_cases():
    # Exactly enough slots; all-tied scores; single node; infeasible.
    free = np.asarray([8, 8], dtype=np.int64)
    scores = np.asarray([1.0, 1.0], dtype=np.float32)
    for n_pods in (1, 2, 4):
        assert (select_gang_slots(scores, free, 4, n_pods)
                == _heap(scores, free, 4, n_pods))
    assert select_gang_slots(scores, free, 8, 3) is None
    one = select_gang_slots(np.asarray([0.5], dtype=np.float32),
                            np.asarray([8], dtype=np.int64), 2, 4,
                            fit_weight=0.5, colocate_bonus=2.0)
    assert one == [0, 0, 0, 0]


def test_decreasing_chains_fall_back_to_heap():
    """Negative colocate bonus violates the top-k precondition; the
    selection must silently use the exact heap path."""
    rng = np.random.default_rng(1)
    for _ in range(50):
        scores, free, request, n_pods, fit, colo = _random_case(
            rng, engineable=False)
        assert not chains_nondecreasing(fit, colo)
        a = select_gang_slots(scores, free, request, n_pods,
                              fit_weight=fit, colocate_bonus=colo)
        b = _heap(scores, free, request, n_pods, fit, colo)
        assert a == b


def test_prefilter_keeps_lowest_indices_among_ties_at_the_cut():
    """At the cut the prefilter keeps, among nodes whose slot-0 value
    equals the threshold, the lowest indices: the heap pops equal
    values lowest index first, so only those can be reached."""
    scores = np.asarray([0.5, 1.0, 0.5, 2.0, 0.5, NEG_INF, 0.5, 1.0],
                        dtype=np.float32)
    slots = np.asarray([1, 1, 1, 1, 1, 0, 1, 1])
    # Above the cut: nodes 3, 1 and 7; then two of the 0.5 ties 0, 2,
    # 4, 6: the lowest two.
    assert _prefilter_np(scores, slots, 5).tolist() == [0, 1, 2, 3, 7]
    assert _prefilter_np(scores, slots, 4).tolist() == [0, 1, 3, 7]
    # All candidates tie: the first n_pods of them.
    flat = np.zeros(8, np.float32)
    assert _prefilter_np(flat, np.ones(8, int), 3).tolist() == [0, 1, 2]
    # A node without slots is no candidate, even at the threshold.
    scores[5] = 0.5
    assert _prefilter_np(scores, slots, 6).tolist() == [0, 1, 2, 3, 4, 7]


# ----------------------------------------------------------------------
# TrackedGroupSum: row patches == from-scratch bincount
# ----------------------------------------------------------------------
def test_tracked_group_sum_patch_equals_bincount(topo):
    state = ClusterState.create(topo)
    state.gpu_busy[1, :3] = True
    snap = FullSnapshotter().take(state)

    def contrib(s, idx):
        if idx is None:
            return s.free_gpus // 4
        return s.free_gpus[idx] // 4

    totals = snap.tracked_sum("t", topo.leaf_id, topo.n_leaf_groups,
                              contrib)
    rng = np.random.default_rng(3)
    for _ in range(30):
        node = int(rng.integers(0, topo.n_nodes))
        k = int(rng.integers(0, 9))
        snap.cols.gpu_busy[node] = False
        snap.cols.gpu_busy[node, :k] = True
        snap._refresh_rows([node])
        scratch = np.bincount(topo.leaf_id,
                              weights=snap.free_gpus // 4,
                              minlength=topo.n_leaf_groups).astype(int)
        assert np.array_equal(totals, scratch)


# ----------------------------------------------------------------------
# Subset level-2 scoring == full-width scoring
# ----------------------------------------------------------------------
class _OwnHealthFilter(HealthFilter):
    """The default HealthFilter under another type: RSCH takes the
    generic Filter path and scores the full width."""


def _full_width_profiles(strategy):
    from repro.core.rsch import profiles_from_config
    profiles = profiles_from_config(RSCHConfig(train_strategy=strategy))
    for prof in (profiles.train, profiles.inference, profiles.best_effort):
        prof.filters = tuple(_OwnHealthFilter() if type(f) is HealthFilter
                             else f for f in prof.filters)
    return profiles


@pytest.mark.parametrize("strategy", list(Strategy))
def test_subset_scoring_matches_full_width(strategy):
    """Subset scoring picks what the full-width batched pass (a Filter
    subclass takes RSCH off the subset path) and the sequential per-pod
    loop over the full width pick."""
    topo = small_topology(n_nodes=64, gpus_per_node=8, nodes_per_leaf=8)
    rng = np.random.default_rng(4)
    for trial in range(10):
        state = ClusterState.create(topo)
        busy = rng.random(64) < 0.5
        count = rng.integers(1, 9, size=64)
        state.gpu_busy[:] = ((np.arange(8) < count[:, None])
                             & busy[:, None])
        snap = FullSnapshotter().take(state)
        job = Job(uid=trial, tenant="t", gpu_type=0,
                  n_pods=int(rng.integers(1, 9)),
                  gpus_per_pod=int(rng.choice([1, 2, 4, 8])),
                  kind=JobKind.TRAIN)
        fast = RSCH(topo, RSCHConfig(train_strategy=strategy))
        full = RSCH(topo, profiles=_full_width_profiles(strategy))
        slow = SequentialRSCH(topo, RSCHConfig(train_strategy=strategy))
        a = fast.schedule(job, snap)
        for b in (full.schedule(job, snap), slow.schedule(job, snap)):
            if a.placement is None:
                assert b.placement is None
            else:
                assert [(p.node, p.gpu_indices)
                        for p in a.placement.pods] \
                    == [(p.node, p.gpu_indices) for p in b.placement.pods]


# ----------------------------------------------------------------------
# Cycle pipelining: byte-identity + speculation accounting
# ----------------------------------------------------------------------
def _sim_jobs(rng, n):
    return [Job(uid=i, tenant=f"t{i % 3}", gpu_type=0,
                n_pods=int(rng.integers(1, 6)),
                gpus_per_pod=int(rng.choice([4, 8])),
                duration=float(rng.integers(600, 8000)),
                submit_time=float(rng.integers(0, 600)),
                priority=int(rng.integers(0, 3)),
                kind=JobKind.TRAIN) for i in range(n)]


def _placements(jobs):
    return [(j.uid, j.start_time,
             None if j.placement is None else
             tuple((p.node, tuple(p.gpu_indices))
                   for p in j.placement.pods))
            for j in sorted(jobs, key=lambda j: j.uid)]


def _run_sim(policy, pipelined, seed=5):
    rng = np.random.default_rng(seed)
    topo = small_topology(n_nodes=24, gpus_per_node=8, nodes_per_leaf=8)
    state = ClusterState.create(topo)
    quota = QuotaManager({f"t{i}": {0: 10 ** 6} for i in range(3)})
    qsch = QSCH(quota, RSCH(topo), QSCHConfig(policy=policy))
    sim = Simulator(state, qsch,
                    SimConfig(pipelined_cycles=pipelined))
    res = sim.run(_sim_jobs(rng, 40))
    return _placements(res.jobs), res


@pytest.mark.parametrize("policy", list(QueuePolicy))
def test_pipelined_cycles_byte_identical(policy):
    a, ra = _run_sim(policy, False)
    b, rb = _run_sim(policy, True)
    assert a == b
    assert ra.pipeline is None
    stats = rb.pipeline
    assert stats is not None
    # Every speculation is eventually accounted: conflicted at arm
    # time, hit/missed at consume time — except at most one still
    # in flight when the run drains.
    drained = stats["hits"] + stats["misses"] + stats["conflicts"]
    assert 0 <= stats["speculated"] - drained <= 1
    assert stats["errors"] == 0


def test_pipeline_hits_under_contention():
    """A fragmentation-blocked head is re-scored every cycle; the
    speculation must be consumed (hit), not recomputed."""
    a, ra = _run_sim(QueuePolicy.BACKFILL, False, seed=6)
    b, rb = _run_sim(QueuePolicy.BACKFILL, True, seed=6)
    assert a == b
    stats = rb.pipeline
    assert stats["speculated"] > 0
    assert stats["hits"] > 0


def test_pipeline_requires_incremental_snapshots(topo, state):
    qsch = make_qsch(topo, state, incremental=False)
    with pytest.raises(ValueError):
        qsch.enable_pipeline()


def test_pipeline_off_is_default(topo, state):
    qsch = make_qsch(topo, state)
    sim = Simulator(state, qsch)
    assert qsch.pipeline is None
    assert SimConfig().pipelined_cycles is False
