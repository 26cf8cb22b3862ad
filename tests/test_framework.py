"""Plugin framework: registry, profiles, extension points, equivalence.

The two hard guarantees of the framework refactor:

1. the default profiles (and the legacy ``Strategy``/``QueuePolicy``
   shims that build them) are placement-identical to the pre-framework
   schedulers;
2. every extension point actually extends: custom plugins change
   behavior without touching scheduler internals.
"""

import numpy as np
import pytest

from repro.core import (ClusterState, Job, JobKind, JobState, QSCH,
                        QSCHConfig, QueuePolicy, QuotaManager, QuotaMode,
                        RSCH, RSCHConfig, SimConfig, Simulator, Strategy,
                        profiles_from_config)
from repro.core.framework import (AdmitPlugin, BackfillPolicy,
                                  FilterPlugin, GfrAwareScore,
                                  PlacementPass, PostBindPlugin, ProfileSet,
                                  QueueSortPlugin, ReservePlugin,
                                  PermitPlugin, ScorePlugin,
                                  SchedulingContext, TenantSoftAffinity,
                                  available_plugins, binpack_pass,
                                  create_plugin, default_profiles,
                                  ebinpack_pass, make_profile, register,
                                  single_pass_plan, spread_pass)
from repro.core.scoring import ScoreWeights
from repro.core.snapshot import FullSnapshotter
from repro.core.topology import ClusterTopology, small_topology
from conftest import make_qsch
from oracles import SequentialRSCH


def _snap(state):
    return FullSnapshotter().take(state)


def _job(uid=0, n_pods=1, gpus=8, kind=JobKind.TRAIN, tenant="t0",
         prio=50, t=0.0):
    return Job(uid=uid, tenant=tenant, gpu_type=0, n_pods=n_pods,
               gpus_per_pod=gpus, kind=kind,
               gang=(kind is JobKind.TRAIN), priority=prio, submit_time=t)


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------
def test_registry_has_builtins_and_contrib():
    names = available_plugins()
    for expected in ("QuotaAdmit", "DynamicFeasibility", "GpuTypeFilter",
                     "HealthFilter", "BinpackScore", "SpreadScore",
                     "GroupConsolidation", "TopoAnchor", "ColocateBonus",
                     "QuotaReserve", "PriorityPreempt",
                     "QuotaReclaimPreempt", "BackfillHeadTimeout",
                     "StrictFIFO", "BestEffortFIFO", "Backfill",
                     "DefaultQueueSort", "GfrAwareScore",
                     "TenantSoftAffinity"):
        assert expected in names


def test_registry_create_and_unknown():
    plugin = create_plugin("ColocateBonus", bonus=3.0)
    assert plugin.per_pod_bonus(_job()) == 3.0
    with pytest.raises(KeyError):
        create_plugin("NoSuchPlugin")


def test_registry_rejects_duplicate_name():
    @register
    class _Dup(ScorePlugin):
        name = "_DupTestPlugin"

    with pytest.raises(ValueError):
        @register
        class _Dup2(ScorePlugin):  # noqa: F811 — intentional clash
            name = "_DupTestPlugin"


# ----------------------------------------------------------------------
# Default-profile equivalence with the legacy shims
# ----------------------------------------------------------------------
def _mixed_trace(n=80, seed=3):
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(8.0, size=n))
    kinds = [JobKind.TRAIN, JobKind.INFER, JobKind.DEBUG]
    jobs = []
    for i in range(n):
        kind = kinds[int(rng.integers(0, 3))]
        gpus = int(rng.choice([1, 2, 4, 8]))
        pods = int(rng.choice([1, 2, 4])) if gpus == 8 else 1
        jobs.append(Job(uid=i, tenant=f"t{i % 2}", gpu_type=0,
                        n_pods=pods, gpus_per_pod=gpus, kind=kind,
                        gang=(kind is JobKind.TRAIN),
                        priority=int(rng.choice([10, 50, 100])),
                        submit_time=float(arrivals[i]),
                        duration=float(rng.exponential(600.0) + 60.0)))
    return jobs


def _run(topo, qsch_kw, rsch):
    state = ClusterState.create(topo, inference_zone_nodes=4)
    qm = QuotaManager({"t0": {0: 10**6}, "t1": {0: 10**6}},
                      mode=QuotaMode.SHARED)
    qsch = QSCH(qm, rsch, **qsch_kw)
    sim = Simulator(state, qsch, SimConfig(tick_interval=30.0,
                                           sample_interval=120.0))
    return sim.run(_mixed_trace())


def _placement_fingerprint(result):
    return [(j.uid, j.state.value, j.start_time, j.requeue_count,
             None if j.placement is None else
             [(p.node, p.gpu_indices, p.nic) for p in j.placement.pods])
            for j in sorted(result.jobs, key=lambda j: j.uid)]


def test_default_profiles_equal_legacy_shim(topo):
    """Explicit default profiles == QSCHConfig/RSCHConfig shims, down to
    every pod's device indices."""
    legacy = _run(topo, dict(config=QSCHConfig(
        policy=QueuePolicy.BACKFILL, backfill_head_timeout=120.0)),
        RSCH(topo, RSCHConfig()))
    explicit = _run(
        topo,
        dict(queue_policy=BackfillPolicy(head_timeout=120.0)),
        RSCH(topo, profiles=default_profiles()))
    assert _placement_fingerprint(legacy) == _placement_fingerprint(explicit)
    assert (legacy.preemptions, legacy.requeues, legacy.infeasible) == \
        (explicit.preemptions, explicit.requeues, explicit.infeasible)


@pytest.mark.parametrize("tstrat,istrat", [
    (Strategy.BINPACK, Strategy.SPREAD),
    (Strategy.E_BINPACK, Strategy.E_SPREAD),
    (Strategy.E_SPREAD, Strategy.E_BINPACK),
])
def test_profiles_from_config_covers_every_strategy(topo, tstrat, istrat):
    cfg = RSCHConfig(train_strategy=tstrat, infer_strategy=istrat)
    legacy = _run(topo, dict(config=QSCHConfig()), RSCH(topo, cfg))
    explicit = _run(topo, dict(config=QSCHConfig()),
                    RSCH(topo, cfg, profiles=profiles_from_config(cfg)))
    assert _placement_fingerprint(legacy) == _placement_fingerprint(explicit)


# ----------------------------------------------------------------------
# Extension points
# ----------------------------------------------------------------------
def test_custom_queue_sort_reorders(topo, state):
    class LargestFirst(QueueSortPlugin):
        name = "LargestFirst"

        def key(self, job):
            return (-job.n_gpus, job.uid)

    profiles = default_profiles()
    profiles.train.queue_sort = LargestFirst()
    qsch = QSCH(QuotaManager({"t0": {0: 1024}}), RSCH(topo,
                profiles=profiles))
    qsch.submit(_job(1, gpus=2))
    qsch.submit(_job(2, gpus=8))
    qsch.submit(_job(3, gpus=4))
    assert [j.uid for j in qsch.pending_jobs()] == [2, 3, 1]


def test_custom_filter_restricts_pool(topo, state):
    class EvenNodesOnly(FilterPlugin):
        name = "EvenNodesOnly"

        def mask(self, job, snap, zone):
            return np.arange(snap.free_gpus.shape[0]) % 2 == 0

    profiles = default_profiles()
    base = profiles.train
    base.filters = base.filters + (EvenNodesOnly(),)
    rsch = RSCH(topo, profiles=profiles)
    for uid in range(4):
        snap = _snap(state)
        r = rsch.schedule(_job(uid, gpus=8), snap)
        assert r.placement is not None
        assert all(p.node % 2 == 0 for p in r.placement.pods)
        state.allocate(_job(uid, gpus=8), r.placement)


def test_filter_subclass_of_builtin_is_not_swallowed(topo, state):
    """A subclass of a built-in filter overriding mask() must go
    through the generic path, not the cached-pool fast path."""
    from repro.core.framework import HealthFilter

    class EvenHealthy(HealthFilter):
        name = "_EvenHealthy"

        def mask(self, job, snap, zone):
            even = np.arange(snap.free_gpus.shape[0]) % 2 == 0
            return snap.node_healthy & even

    profiles = default_profiles()
    profiles.train.filters = (
        profiles.train.filters[0],    # GpuTypeFilter
        EvenHealthy(),
    )
    rsch = RSCH(topo, profiles=profiles)
    r = rsch.schedule(_job(1, n_pods=4, gpus=8), _snap(state))
    assert r.placement is not None
    assert all(p.node % 2 == 0 for p in r.placement.pods)


def test_feasible_honors_custom_filter_chain(topo, state):
    """Dynamic admission must see the same pool placement does; a
    restrictive Filter plugin must not create an admit-pass /
    place-fail requeue loop."""
    class NothingFits(FilterPlugin):
        name = "_NothingFits"

        def mask(self, job, snap, zone):
            return np.zeros(snap.free_gpus.shape[0], dtype=bool)

    profiles = default_profiles()
    profiles.train.filters = profiles.train.filters + (NothingFits(),)
    rsch = RSCH(topo, profiles=profiles)
    assert not rsch.feasible(_job(1, gpus=8), _snap(state))
    qsch = QSCH(QuotaManager({"t0": {0: 1024}}), rsch)
    qsch.submit(_job(1, gpus=8))
    res = qsch.cycle(state, 0.0)
    assert res.scheduled == []
    assert res.infeasible == 1
    assert res.requeues == 0          # rejected at admission, not requeued
    assert qsch.pending_jobs()[0].requeue_count == 0


@pytest.mark.parametrize("batched", [True, False])
def test_custom_score_plugin_changes_placement(topo, state, batched):
    """An additive Score term flips the winner, in the batched slot walk
    and in the sequential oracle alike."""
    class PreferNode(ScorePlugin):
        name = "_PreferNode"

        def __init__(self, node, weight=100.0):
            self.node = node
            self.weight = weight

        def score(self, job, snap, pool, ctx):
            term = np.zeros(snap.free_gpus.shape[0], dtype=np.float32)
            term[self.node] = self.weight
            return term

    # node 3 sits inside the preselected NodeNetGroup (nodes 0-3); the
    # extra term must beat binpack's default lowest-index pick (node 0).
    profiles = ProfileSet(
        train=make_profile("t", single_pass_plan(PlacementPass(
            scorers=(create_plugin("BinpackScore"), PreferNode(3))))),
        inference=make_profile("i", single_pass_plan(binpack_pass())),
        best_effort=make_profile("b", single_pass_plan(binpack_pass())),
    )
    rsch_class = RSCH if batched else SequentialRSCH
    rsch = rsch_class(topo, profiles=profiles)
    r = rsch.schedule(_job(1, gpus=4), _snap(state))
    assert r.placement.pods[0].node == 3


@pytest.mark.parametrize("n_pods,gpus", [(4, 8), (8, 4), (12, 2)])
def test_batched_matches_sequential_with_extra_scorer(topo, n_pods, gpus):
    """Parity with the sequential oracle must survive non-fused score
    terms."""
    rng = np.random.default_rng(42)
    state = ClusterState.create(topo)
    for node in range(state.n_nodes):
        k = int(rng.integers(0, 7))
        if k:
            state.gpu_busy[node, :k] = True
    snap = _snap(state)
    job = _job(1, n_pods=n_pods, gpus=gpus)

    def mk(rsch_class):
        profiles = ProfileSet(
            train=make_profile("t", single_pass_plan(ebinpack_pass(
                colocate=2.0, extra_scorers=(GfrAwareScore(weight=3.0),)))),
            inference=make_profile("i", single_pass_plan(spread_pass())),
            best_effort=make_profile("b", single_pass_plan(binpack_pass())),
        )
        return rsch_class(topo, profiles=profiles)

    rb = mk(RSCH).schedule(job, snap)
    rs = mk(SequentialRSCH).schedule(job, snap)
    assert (rb.placement is None) == (rs.placement is None)
    if rb.placement is not None:
        assert [(p.node, p.gpu_indices) for p in rb.placement.pods] == \
            [(p.node, p.gpu_indices) for p in rs.placement.pods]


def test_custom_admit_plugin_rejects_and_counts(topo, state):
    class MaxSizeAdmit(AdmitPlugin):
        name = "_MaxSizeAdmit"
        stage = "static"

        def admit(self, job, ctx):
            return job.n_gpus <= 8

    profiles = default_profiles()
    for prof in (profiles.train, profiles.inference, profiles.best_effort):
        prof.admit = prof.admit + (MaxSizeAdmit(),)
    qsch = QSCH(QuotaManager({"t0": {0: 1024}}),
                RSCH(topo, profiles=profiles))
    qsch.submit(_job(1, n_pods=4, gpus=8))      # 32 GPUs: rejected
    qsch.submit(_job(2, gpus=8))                # admitted
    res = qsch.cycle(state, 0.0)
    assert [j.uid for j in res.scheduled] == [2]
    assert res.admit_rejected == 1
    assert qsch.queue_depth() == 1


def test_permit_veto_rolls_back_reservations(topo, state):
    events = []

    class SpyReserve(ReservePlugin):
        name = "_SpyReserve"

        def reserve(self, job, placement, ctx):
            events.append(("reserve", job.uid))
            return True

        def unreserve(self, job, placement, ctx):
            events.append(("unreserve", job.uid))

    class VetoAll(PermitPlugin):
        name = "_VetoAll"

        def permit(self, job, placement, ctx):
            return False

    profiles = default_profiles()
    profiles.train.reserve = profiles.train.reserve + (SpyReserve(),)
    profiles.train.permit = (VetoAll(),)
    qm = QuotaManager({"t0": {0: 1024}})
    qsch = QSCH(qm, RSCH(topo, profiles=profiles))
    qsch.submit(_job(1, gpus=8))
    res = qsch.cycle(state, 0.0)
    assert res.scheduled == []
    assert res.requeues == 1
    # transactional: quota charged then refunded, spy rolled back
    assert qm.tenant_used("t0", 0) == 0
    assert events == [("reserve", 1), ("unreserve", 1)]
    assert state.total_allocated() == 0
    job = qsch.pending_jobs()[0]
    assert job.requeue_count == 1 and job.state is JobState.PENDING


def test_post_bind_plugin_invoked(topo, state):
    bound = []

    class RecordBind(PostBindPlugin):
        name = "_RecordBind"

        def post_bind(self, job, placement, ctx):
            bound.append((job.uid, len(placement.pods)))

    profiles = default_profiles()
    profiles.train.post_bind = (RecordBind(),)
    qsch = QSCH(QuotaManager({"t0": {0: 1024}}),
                RSCH(topo, profiles=profiles))
    qsch.submit(_job(1, n_pods=2, gpus=8))
    res = qsch.cycle(state, 0.0)
    assert [j.uid for j in res.scheduled] == [1]
    assert bound == [(1, 2)]


# ----------------------------------------------------------------------
# Contrib plugins
# ----------------------------------------------------------------------
def test_gfr_aware_score_heals_fragmented_node(topo, state):
    # node 3 fragmented with an exact 4-GPU hole; node 0..: idle.
    state.gpu_busy[3, :4] = True
    profiles = ProfileSet(
        train=make_profile("t", single_pass_plan(PlacementPass(
            scorers=(create_plugin("SpreadScore"),
                     GfrAwareScore(weight=10.0))))),
        inference=make_profile("i", single_pass_plan(spread_pass())),
        best_effort=make_profile("b", single_pass_plan(spread_pass())),
    )
    rsch = RSCH(topo, profiles=profiles)
    r = rsch.schedule(_job(1, gpus=4), _snap(state))
    # Spread alone would avoid node 3; the GFR term overrides it.
    assert r.placement.pods[0].node == 3
    baseline = RSCH(topo, RSCHConfig(train_strategy=Strategy.SPREAD))
    rb = baseline.schedule(_job(1, gpus=4), _snap(state))
    assert rb.placement.pods[0].node != 3


def test_tenant_soft_affinity_groups_tenant(topo, state):
    rsch_default = RSCH(topo)
    # Tenant A runs a job in some group; an unrelated tenant too.
    running = {}
    for uid, tenant, node_hint in ((10, "a", None), (11, "b", None)):
        j = Job(uid=uid, tenant=tenant, gpu_type=0, n_pods=1,
                gpus_per_pod=2, kind=JobKind.TRAIN)
        r = rsch_default.schedule(j, _snap(state))
        state.allocate(j, r.placement)
        j.placement = r.placement
        running[uid] = j
    group_of = {j.tenant: int(topo.leaf_id[j.placement.pods[0].node])
                for j in running.values()}

    affinity = TenantSoftAffinity(topo, weight=50.0, anti_weight=50.0)
    profiles = ProfileSet(
        train=make_profile("t", single_pass_plan(PlacementPass(
            scorers=(create_plugin("SpreadScore"), affinity)))),
        inference=make_profile("i", single_pass_plan(spread_pass())),
        best_effort=make_profile("b", single_pass_plan(spread_pass())),
    )
    rsch = RSCH(topo, profiles=profiles)
    ctx = SchedulingContext(running=running)
    ra = rsch.schedule(_job(1, gpus=2, tenant="a"), _snap(state), ctx)
    assert int(topo.leaf_id[ra.placement.pods[0].node]) == group_of["a"]
    # And without context the term vanishes (no crash, spread behavior).
    rn = rsch.schedule(_job(2, gpus=2, tenant="a"), _snap(state))
    assert rn.placement is not None


# ----------------------------------------------------------------------
# Counters (admission-rejection / requeue accounting)
# ----------------------------------------------------------------------
def test_cycle_counters_quota_and_infeasible(topo, state):
    qsch = make_qsch(topo, state, quota={"t0": {0: 8}})
    qsch.submit(_job(1, gpus=8))
    qsch.submit(_job(2, gpus=8))      # over quota -> admit_rejected
    res = qsch.cycle(state, 0.0)
    assert [j.uid for j in res.scheduled] == [1]
    assert res.admit_rejected == 1

    qsch2 = make_qsch(topo, state)
    qsch2.submit(_job(3, n_pods=32, gpus=8))   # 32 nodes > cluster
    res2 = qsch2.cycle(state, 0.0)
    assert res2.scheduled == []
    assert res2.infeasible >= 1


def test_sim_result_aggregates_counters(topo):
    state = ClusterState.create(topo)
    qm = QuotaManager({"t0": {0: 32}})       # tight quota forces waits
    qsch = QSCH(qm, RSCH(topo), QSCHConfig())
    sim = Simulator(state, qsch, SimConfig(tick_interval=30.0,
                                           sample_interval=300.0))
    jobs = [_job(uid, gpus=8, t=float(uid)) for uid in range(8)]
    for j in jobs:
        j.duration = 120.0
    result = sim.run(jobs)
    assert all(j.state is JobState.COMPLETED for j in result.jobs)
    assert result.admit_rejected > 0      # quota made some jobs wait
    assert result.requeues == 0
