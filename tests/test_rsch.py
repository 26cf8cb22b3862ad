"""RSCH: strategies, gang semantics, device-level selection (§3.3)."""

import numpy as np
import pytest

from repro.core import (ClusterState, Job, JobKind, RSCH, RSCHConfig,
                        Strategy)
from repro.core.snapshot import FullSnapshotter
from repro.core.topology import ClusterTopology, small_topology


def _rsch(topo, **kw):
    return RSCH(topo, RSCHConfig(**kw))


def _snap(state):
    return FullSnapshotter().take(state)


def _train_job(uid=0, n_pods=1, gpus=8, prio=50):
    return Job(uid=uid, tenant="t0", gpu_type=0, n_pods=n_pods,
               gpus_per_pod=gpus, kind=JobKind.TRAIN, priority=prio)


def _infer_job(uid=0, n_pods=2, gpus=2):
    return Job(uid=uid, tenant="t0", gpu_type=0, n_pods=n_pods,
               gpus_per_pod=gpus, kind=JobKind.INFER, gang=False)


def test_binpack_prefers_used_nodes(topo, state):
    rsch = _rsch(topo, train_strategy=Strategy.BINPACK)
    j1 = _train_job(uid=1, gpus=4)
    r1 = rsch.schedule(j1, _snap(state))
    state.allocate(j1, r1.placement)
    j2 = _train_job(uid=2, gpus=4)
    r2 = rsch.schedule(j2, _snap(state))
    # exact-fit + used bonus -> same node as j1
    assert r2.placement.pods[0].node == r1.placement.pods[0].node


def test_spread_prefers_idle_nodes(topo, state):
    rsch = _rsch(topo, infer_strategy=Strategy.SPREAD)
    j1 = _infer_job(uid=1, n_pods=1, gpus=2)
    r1 = rsch.schedule(j1, _snap(state))
    state.allocate(j1, r1.placement)
    j2 = _infer_job(uid=2, n_pods=1, gpus=2)
    r2 = rsch.schedule(j2, _snap(state))
    assert r2.placement.pods[0].node != r1.placement.pods[0].node


def test_gang_all_or_nothing(topo, state):
    rsch = _rsch(topo)
    # 17 whole-node pods > 16 nodes -> must fail with no mutation
    big = _train_job(uid=1, n_pods=17, gpus=8)
    res = rsch.schedule(big, _snap(state))
    assert res.placement is None
    assert state.total_allocated() == 0


@pytest.mark.parametrize("backend", ["np", "interpret"])
def test_gang_placement_enters_rsch_phases(topo, state, backend):
    """A gang placement runs inside ``rsch-schedule``, which holds the
    level-1 ``group-choice`` and the ``slot-walk``, and carries the
    job's uid; the device backend's score call sits inside it too."""
    from conftest import PhaseLog
    rsch = _rsch(topo, score_backend=backend)
    rsch.obs = log = PhaseLog()
    res = rsch.schedule(_train_job(uid=7, n_pods=3, gpus=8), _snap(state))
    assert res.placement is not None
    outer = log.interval("rsch-schedule")
    inner = ["group-choice", "slot-walk"]
    if backend == "interpret":
        inner.append("score-fetch")
    for name in inner:
        lo, hi = log.interval(name)
        assert outer[0] < lo < hi < outer[1], name
    assert log.interval("group-choice")[1] < log.interval("slot-walk")[0]
    assert log.uids == {"rsch-schedule": 7}


def test_feasible_checks_pool(topo, state):
    rsch = _rsch(topo)
    snap = _snap(state)
    assert rsch.feasible(_train_job(n_pods=16, gpus=8), snap)
    assert not rsch.feasible(_train_job(n_pods=17, gpus=8), snap)


def test_ebinpack_consolidates_groups(topo, state):
    """LeafGroup-level E-Binpack: small jobs land in the busiest group."""
    rsch = _rsch(topo, train_strategy=Strategy.E_BINPACK)
    j1 = _train_job(uid=1, gpus=8)
    r1 = rsch.schedule(j1, _snap(state))
    state.allocate(j1, r1.placement)
    seed_group = int(topo.leaf_id[r1.placement.pods[0].node])
    for uid in range(2, 5):
        j = _train_job(uid=uid, gpus=8)
        r = rsch.schedule(j, _snap(state))
        state.allocate(j, r.placement)
        assert int(topo.leaf_id[r.placement.pods[0].node]) == seed_group


def test_multi_group_job_minimizes_groups(topo, state):
    rsch = _rsch(topo, train_strategy=Strategy.E_BINPACK)
    # 8 whole nodes = 2 full leaf groups (4 nodes each)
    j = _train_job(uid=1, n_pods=8, gpus=8)
    r = rsch.schedule(j, _snap(state))
    assert r.placement is not None
    groups = {int(topo.leaf_id[p.node]) for p in r.placement.pods}
    assert len(groups) == 2


def test_espread_uses_dedicated_zone(topo):
    state = ClusterState.create(topo, inference_zone_nodes=4)
    rsch = _rsch(topo, infer_strategy=Strategy.E_SPREAD)
    j = _infer_job(uid=1, n_pods=2, gpus=2)
    r = rsch.schedule(j, _snap(state))
    assert r.placement is not None
    for pod in r.placement.pods:
        assert pod.node < 4        # inside the zone


def test_espread_large_pods_fall_back_to_general_pool(topo):
    state = ClusterState.create(topo, inference_zone_nodes=4)
    rsch = _rsch(topo, infer_strategy=Strategy.E_SPREAD)
    j = Job(uid=2, tenant="t0", gpu_type=0, n_pods=1, gpus_per_pod=8,
            kind=JobKind.INFER, gang=False)
    r = rsch.schedule(j, _snap(state))
    assert r.placement is not None
    assert r.placement.pods[0].node >= 4   # E-Binpack outside the zone


def test_device_selection_prefers_one_island():
    topo = ClusterTopology(n_nodes=1, gpus_per_node=8, nodes_per_leaf=1,
                           leaves_per_spine=1, spines_per_superspine=1,
                           nodes_per_hbd=1, nvlink_island=4, numa_split=4)
    state = ClusterState.create(topo)
    rsch = _rsch(topo)
    # occupy gpu 0 and 1 -> island 0 has 2 free, island 1 has 4 free
    state.gpu_busy[0, 0] = state.gpu_busy[0, 1] = True
    gpus = rsch._pick_devices(state.gpu_busy[0], state.gpu_healthy[0], 4)
    assert set(gpus) == {4, 5, 6, 7}       # the intact island
    nic = topo.nic_for_gpu()
    assert len({int(nic[g]) for g in gpus}) == 1


def test_unhealthy_devices_skipped(topo, state):
    rsch = _rsch(topo)
    state.set_gpu_health(0, 3, False)
    j = _train_job(uid=1, gpus=8)
    r = rsch.schedule(j, _snap(state))
    assert r.placement is not None
    assert r.placement.pods[0].node != 0   # node 0 has only 7 healthy


# ----------------------------------------------------------------------
# Batched gang placement (§3.4): one fused pass must equal the per-pod
# sequential loop — same nodes, same order, same devices.
# ----------------------------------------------------------------------
def _fragment(state, rng):
    for node in range(state.n_nodes):
        k = int(rng.integers(0, state.gpus_per_node + 1))
        if k and rng.random() < 0.6:
            free = np.nonzero(~state.gpu_busy[node])[0][:k]
            state.gpu_busy[node, free] = True


@pytest.mark.parametrize("strategy", list(Strategy))
@pytest.mark.parametrize("n_pods,gpus", [(1, 8), (4, 8), (8, 4), (12, 2)])
def test_batched_matches_sequential(topo, strategy, n_pods, gpus):
    import zlib
    rng = np.random.default_rng(
        zlib.crc32(f"{strategy.value}-{n_pods}-{gpus}".encode()))
    state = ClusterState.create(topo)
    _fragment(state, rng)
    state.set_gpu_health(1, 0, False)
    snap = _snap(state)
    kind = JobKind.INFER if strategy in (Strategy.SPREAD,
                                         Strategy.E_SPREAD) else JobKind.TRAIN
    job = Job(uid=1, tenant="t0", gpu_type=0, n_pods=n_pods,
              gpus_per_pod=gpus, kind=kind, gang=(kind is JobKind.TRAIN))
    kw = dict(train_strategy=strategy, infer_strategy=strategy)
    rb = _rsch(topo, batched_gang=True, **kw).schedule(job, snap)
    rs = _rsch(topo, batched_gang=False, **kw).schedule(job, snap)
    assert (rb.placement is None) == (rs.placement is None)
    if rb.placement is not None:
        assert ([(p.node, p.gpu_indices) for p in rb.placement.pods]
                == [(p.node, p.gpu_indices) for p in rs.placement.pods])


def test_batched_slot_expansion_colocates(topo, state):
    """A node contributes floor(free/gpus_per_pod) slots; the co-location
    bonus folded into the slot chain keeps the gang on one node."""
    rsch = _rsch(topo, train_strategy=Strategy.E_BINPACK)
    j = Job(uid=1, tenant="t0", gpu_type=0, n_pods=4, gpus_per_pod=2,
            kind=JobKind.TRAIN)
    r = rsch.schedule(j, _snap(state))
    assert r.placement is not None
    assert len({p.node for p in r.placement.pods}) == 1


def test_batched_gang_all_or_nothing(topo, state):
    rsch = _rsch(topo, batched_gang=True)
    res = rsch.schedule(_train_job(uid=1, n_pods=17, gpus=8), _snap(state))
    assert res.placement is None
    assert state.total_allocated() == 0


def test_select_gang_slots_insufficient_capacity():
    from repro.core.scoring import NEG_INF, select_gang_slots
    scores = np.asarray([1.0, NEG_INF, 0.5], dtype=np.float32)
    free = np.asarray([8, 8, 4])
    assert select_gang_slots(scores, free, 4, 4) is None     # 3 slots < 4
    picks = select_gang_slots(scores, free, 4, 3)
    assert picks == [0, 0, 2]                                # 2+1 slots
