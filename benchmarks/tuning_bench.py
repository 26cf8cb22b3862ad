"""Self-tuning benchmark: inert when idle, profitable when active,
transferable across clusters, cheap at scale.

Four gates, matching the tuning subsystem's acceptance criteria:

1. **Byte-identity** — a :class:`repro.core.TuningManager` attached
   with a :class:`NoOpController` must not perturb the simulation:
   across a policy x strategy matrix, placements, metric reports and
   the raw sample series are identical to the detached run, and the
   param-change log stays empty.
2. **Tuned vs static** — on a contended multi-priority drain trace
   (large low-priority gangs behind a stream of small normal-priority
   jobs), the tuned controller stack (starvation escalator + guarded
   hill climb) must beat EVERY static Table-1 profile on at least one
   frontier metric (GAR, mean GFR, P90 JWTD, goodput) without
   regressing any other beyond a per-metric noise tolerance.
3. **Warm-start transfer** — a federation member warm-started from a
   donor member's exported :class:`repro.core.TuningProfile` reaches
   the donor's tuned operating point (L-inf distance in range-
   normalized parameter space) in measurably fewer control periods
   than an identical cold-started member.
4. **Attached overhead** — with the manager attached and its tick-path
   live (wait harvest + controller scans), the per-cycle scheduling
   cost on a fragmented 10k-node cluster stays within **5%** of the
   detached cycle, measured by the median of paired per-iteration
   deltas on one shared stack.

Writes ``BENCH_tuning.json`` (uploaded as a CI artifact).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

if __package__ in (None, ""):   # `python benchmarks/tuning_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import (bench_seed, clone_jobs, scale_topology,
                               write_bench_json)  # noqa: E402
from benchmarks.obs_bench import (placement_fingerprint,
                                  sample_series)  # noqa: E402
from repro.core import (ClusterState, Event, EventKind, FederatedCluster,
                        FederatedSimulator, HillClimbController, Job,
                        JobKind, NoOpController, PRIO_LOW, PRIO_NORMAL,
                        QSCH, QSCHConfig, QueuePolicy, QuotaManager,
                        RSCH, RSCHConfig, SimConfig, Simulator, SimResult,
                        StarvationEscalator, Strategy, TuningManager,
                        make_member, training_trace,
                        waiting_percentile)  # noqa: E402
from repro.core.topology import ClusterTopology  # noqa: E402

CONTROL_PERIOD_S = 1800.0


def run_sim(jobs: Sequence[Job], *, policy=QueuePolicy.BACKFILL,
            strategy=Strategy.E_BINPACK, n_gpus: int = 512,
            manager: Optional[TuningManager] = None,
            preempt: bool = True,
            horizon: Optional[float] = None) -> SimResult:
    topo = scale_topology(n_gpus=n_gpus)
    state = ClusterState.create(topo)
    qm = QuotaManager({"t0": {0: 10**6}})
    rsch = RSCH(topo, RSCHConfig(train_strategy=strategy))
    qsch = QSCH(qm, rsch, QSCHConfig(policy=policy,
                                     priority_preemption=preempt))
    sim = Simulator(state, qsch,
                    SimConfig(tick_interval=30.0, sample_interval=300.0,
                              binding_latency=45.0, horizon=horizon))
    if manager is not None:
        manager.attach(sim)
    return sim.run(clone_jobs(jobs))


# ----------------------------------------------------------------------
# 1. Byte-identity: an attached no-op manager must not perturb the run
# ----------------------------------------------------------------------
def identity_gate(seed: int, smoke: bool) -> Dict:
    jobs = training_trace(80 if smoke else 160, seed=seed,
                          arrival_rate_per_hour=500,
                          mean_duration_s=2400.0)
    jobs = [j for j in jobs if j.n_gpus <= 128]
    configs = [(QueuePolicy.BACKFILL, Strategy.E_BINPACK),
               (QueuePolicy.STRICT_FIFO, Strategy.BINPACK),
               (QueuePolicy.BEST_EFFORT_FIFO, Strategy.E_BINPACK)]
    if not smoke:
        configs += [(QueuePolicy.BACKFILL, Strategy.BINPACK),
                    (QueuePolicy.STRICT_FIFO, Strategy.E_BINPACK),
                    (QueuePolicy.BEST_EFFORT_FIFO, Strategy.BINPACK)]
    handles = 0
    for policy, strategy in configs:
        base = run_sim(jobs, policy=policy, strategy=strategy)
        noop = NoOpController()
        mgr = TuningManager([noop], control_period_s=CONTROL_PERIOD_S)
        inst = run_sim(jobs, policy=policy, strategy=strategy,
                       manager=mgr)
        tag = f"{policy.name} x {strategy.name}"
        assert placement_fingerprint(base) == placement_fingerprint(
            inst), f"no-op manager perturbed placements: {tag}"
        assert base.metrics.report() == inst.metrics.report(), \
            f"no-op manager perturbed the metric report: {tag}"
        assert sample_series(base) == sample_series(inst), \
            f"no-op manager perturbed the raw sample series: {tag}"
        assert noop.ticks_seen > 0 and noop.windows_seen > 0, \
            f"manager never drove the controller: {tag}"
        assert not mgr.space.changes, \
            f"no-op run wrote {len(mgr.space.changes)} param changes"
        handles = len(mgr.space)
        assert handles >= 15, \
            f"expected a full tunable surface, got {handles} handles"
    print(f"--- identity: {len(configs)} policy x strategy configs "
          f"byte-identical with an attached no-op manager "
          f"({handles} tunable handles bound)")
    return {"configs_checked": len(configs), "handles": handles}


# ----------------------------------------------------------------------
# 2. Tuned controller vs the static Table-1 profiles
# ----------------------------------------------------------------------
def contended_trace(seed: int, smoke: bool, n_gpus: int) -> List[Job]:
    """Starvation-shaped contention: a substantial PRIO_LOW class
    (8/16-GPU pods, ~2.4x cluster capacity) bursts in at t=0 under a
    continuous stream of small PRIO_NORMAL jobs.  Priority ordering
    keeps the stream ahead of the queued low jobs, so without
    escalation they only drain through leftover capacity for hours —
    their waits dominate the P90 JWTD."""
    rng = np.random.default_rng(seed)
    window = 4.0 * 3600.0
    jobs: List[Job] = []
    # Normal-priority stream: ~55% average utilization on its own.
    n_norm = round(0.55 * n_gpus * window / (4.9 * 2400.0))
    inter = rng.exponential(window / n_norm, size=n_norm)
    arrivals = np.cumsum(inter)
    for i in range(n_norm):
        gpus = int(rng.choice([1, 2, 4, 8, 16], p=[.2, .25, .25, .2, .1]))
        n_pods, per_pod = (1, gpus) if gpus <= 8 else (gpus // 8, 8)
        jobs.append(Job(uid=i, tenant="t0", gpu_type=0, n_pods=n_pods,
                        gpus_per_pod=per_pod, priority=PRIO_NORMAL,
                        submit_time=float(arrivals[i]),
                        duration=max(300.0, float(
                            rng.exponential(2400.0)))))
    # Low-priority burst: ~2.4x cluster capacity submitted in the first
    # ten minutes, so a deep low-priority backlog forms immediately.
    n_low = round(2.4 * n_gpus / 11.2)
    for k in range(n_low):
        gpus = int(rng.choice([8, 16], p=[.6, .4]))
        jobs.append(Job(uid=50_000 + k, tenant="t0", gpu_type=0,
                        n_pods=gpus // 8, gpus_per_pod=8,
                        kind=JobKind.TRAIN, priority=PRIO_LOW,
                        submit_time=float(rng.uniform(0.0, 600.0)),
                        duration=max(300.0, float(
                            rng.exponential(2400.0)))))
    return jobs


def frontier_metrics(result: SimResult) -> Dict[str, float]:
    rep = result.metrics.report()
    return {"gar": float(rep["median_gar"]),
            "gfr": float(rep["mean_gfr"]),
            "p90_wait": float(waiting_percentile(result.jobs, 90.0)),
            "p99_wait": float(waiting_percentile(result.jobs, 99.0)),
            "goodput": float(rep["goodput_gpu_seconds"])}


# Per-metric comparison: sense (+1 higher-better / -1 lower-better),
# relative noise tolerance, absolute slack (dominates near zero).
# P99 is the starvation tail the escalator targets; P90 sits in the
# bulk of the distribution and is tracked as a no-regression guard.
METRIC_SENSE = {"gar": +1, "gfr": -1, "p90_wait": -1, "p99_wait": -1,
                "goodput": +1}
METRIC_TOL = {"gar": (0.05, 0.02), "gfr": (0.05, 0.02),
              "p90_wait": (0.10, 120.0), "p99_wait": (0.10, 120.0),
              "goodput": (0.02, 0.0)}


def compare_arm(tuned: Dict[str, float], static: Dict[str, float]
                ) -> Tuple[List[str], List[str]]:
    """(wins, regressions) of the tuned arm against one static arm."""
    wins, regressions = [], []
    for name, sense in METRIC_SENSE.items():
        rel, slack = METRIC_TOL[name]
        margin = abs(static[name]) * rel + slack
        gain = sense * (tuned[name] - static[name])
        if gain > margin:
            wins.append(name)
        elif gain < -margin:
            regressions.append(name)
    return wins, regressions


def tuned_vs_static_gate(seed: int, smoke: bool) -> Dict:
    n_gpus = 512 if smoke else 1024
    jobs = contended_trace(seed, smoke, n_gpus)
    statics = {f"static:{s.name}": s
               for s in (Strategy.E_BINPACK, Strategy.BINPACK,
                         Strategy.E_SPREAD, Strategy.SPREAD)}
    # Priority preemption is off in EVERY arm: the gate isolates what
    # the controllers buy through queue ordering and knob tuning alone,
    # without eviction churn in either arm.
    arms: Dict[str, Dict[str, float]] = {}
    for tag, strategy in statics.items():
        arms[tag] = frontier_metrics(run_sim(jobs, strategy=strategy,
                                             n_gpus=n_gpus,
                                             preempt=False))
    mgr = TuningManager(
        [StarvationEscalator(wait_threshold_s=900.0, boost=30,
                             escalation_period_s=450.0),
         HillClimbController(seed=seed, params=["qsch."],
                             hysteresis=0.02)],
        control_period_s=CONTROL_PERIOD_S)
    tuned_result = run_sim(jobs, strategy=Strategy.E_BINPACK,
                           n_gpus=n_gpus, manager=mgr, preempt=False)
    tuned = frontier_metrics(tuned_result)
    escalator = mgr.controllers[0]
    climber = mgr.controllers[1]
    assert escalator.escalations > 0, \
        "contended trace never triggered the starvation escalator"
    matchups = {}
    for tag, static in arms.items():
        wins, regressions = compare_arm(tuned, static)
        matchups[tag] = {"wins": wins, "regressions": regressions}
        assert wins, (f"tuned arm beat {tag} on no frontier metric: "
                      f"tuned={tuned} static={static}")
        assert not regressions, (
            f"tuned arm regressed {regressions} vs {tag}: "
            f"tuned={tuned} static={static}")
    print(f"--- tuned vs static: beat all {len(arms)} Table-1 profiles "
          f"(P90 wait {tuned['p90_wait']:.0f}s vs "
          f"{arms['static:E_BINPACK']['p90_wait']:.0f}s on the base "
          f"profile; {escalator.escalations} escalations, "
          f"{climber.moves} probes / {climber.reverts} reverts)")
    for tag in arms:
        print(f"    vs {tag}: wins={matchups[tag]['wins']}")
    return {"n_gpus": n_gpus, "tuned": tuned, "static": arms,
            "matchups": matchups,
            "escalations": escalator.escalations,
            "probes": climber.moves, "accepts": climber.accepts,
            "reverts": climber.reverts,
            "control_periods": mgr.periods}


# ----------------------------------------------------------------------
# 3. Warm-start transfer across federation members
# ----------------------------------------------------------------------
def _make_fed(n_nodes: int) -> FederatedCluster:
    return FederatedCluster([
        make_member("dc-a", gpu_pools=((0, n_nodes),), region="west"),
        make_member("dc-b", gpu_pools=((0, n_nodes),), region="west"),
    ])


def _fed_trace(seed: int, smoke: bool, n_gpus: int) -> List[Job]:
    rng = np.random.default_rng(seed)
    window = (4.0 if smoke else 6.0) * 3600.0
    n_jobs = 160 if smoke else 280
    inter = rng.exponential(window / n_jobs, size=n_jobs)
    arrivals = np.cumsum(inter)
    jobs = []
    for i in range(n_jobs):
        gpus = int(rng.choice([4, 8, 16, 32], p=[.3, .35, .2, .15]))
        n_pods, per_pod = (1, gpus) if gpus <= 8 else (gpus // 8, 8)
        jobs.append(Job(uid=i, tenant="t0", gpu_type=0, n_pods=n_pods,
                        gpus_per_pod=per_pod,
                        submit_time=float(arrivals[i]),
                        duration=max(600.0, float(
                            rng.exponential(3000.0)))))
    return jobs


def _normalized_linf(space, a: Dict[str, float], b: Dict[str, float]
                     ) -> float:
    """L-inf distance between two operating points, each coordinate
    normalized by its handle's bound range."""
    worst = 0.0
    for name in a:
        if name not in b or name not in space:
            continue
        p = space.param(name)
        span = p.hi - p.lo
        if span <= 0:
            continue
        worst = max(worst, abs(a[name] - b[name]) / span)
    return worst


CONVERGE_TOL = 0.03     # within 3% of every handle's range


def _periods_to_converge(space, snapshots: Sequence[Dict[str, float]],
                         target: Dict[str, float]) -> int:
    for i, snap in enumerate(snapshots):
        if _normalized_linf(space, target, snap) <= CONVERGE_TOL:
            return i
    return len(snapshots)   # never converged within the run


def warm_start_gate(seed: int, smoke: bool) -> Dict:
    n_nodes = 32
    jobs = _fed_trace(seed, smoke, n_nodes * 8)

    def run_member(member: int, donor=None, climb_seed: int = 0):
        fed = _make_fed(n_nodes)
        fs = FederatedSimulator(fed)
        mgr = TuningManager(
            [HillClimbController(seed=climb_seed, hysteresis=0.0,
                                 epsilon=0.3)],
            control_period_s=CONTROL_PERIOD_S)
        mgr.attach(fs.sims[member], scope=fed.members[member].name,
                   gsch=fs.gsch)
        defaults = mgr.space.snapshot()     # stack defaults
        if donor is not None:
            skipped = mgr.warm_start(donor)
            assert not skipped, f"donor params without handles: {skipped}"
        start = mgr.space.snapshot()        # period-0 operating point
        fs.run(clone_jobs(jobs))
        return mgr, defaults, start

    # Donor: tune member dc-a, export its operating point.
    donor_mgr, defaults, _ = run_member(0, climb_seed=seed)
    donor = donor_mgr.export_profile("dc-a-tuned")
    moved = _normalized_linf(donor_mgr.space, defaults, donor.params)
    assert moved > CONVERGE_TOL, (
        f"donor run moved no parameter beyond tolerance ({moved:.3f}); "
        f"the transfer gate needs a tuned donor")
    payload = donor.to_json()          # exercise the wire format
    donor = type(donor).from_json(payload)

    # Recipients: identical member (dc-b), identical trace — one cold,
    # one warm-started from the donor profile.  A member's trajectory
    # is its period-0 operating point plus the end-of-period snapshots;
    # convergence = first trajectory point within tolerance of the
    # donor's operating point.
    cold, _, cold_start = run_member(1, climb_seed=seed + 1)
    warm, _, warm_start = run_member(1, donor=donor, climb_seed=seed + 1)

    cold_periods = _periods_to_converge(
        cold.space, [cold_start] + cold.period_snapshots, donor.params)
    warm_traj = [warm_start] + warm.period_snapshots
    warm_periods = _periods_to_converge(warm.space, warm_traj,
                                        donor.params)
    # The warm member STARTS at the donor point (period 0); the cold
    # member has to re-walk there, which the guarded climb does not do
    # within the run.
    assert warm_periods < cold_periods, (
        f"warm start did not converge faster: warm={warm_periods} "
        f"cold={cold_periods} periods (of {warm.periods} run)")
    warm_d0 = _normalized_linf(warm.space, donor.params, warm_traj[0]) \
        if warm_traj else float("nan")
    print(f"--- warm start: donor moved {moved:.3f} (range-normalized "
          f"L-inf) over {donor_mgr.periods} periods; warm member at the "
          f"donor point after {warm_periods} periods "
          f"(d0={warm_d0:.3f}) vs cold {cold_periods}+ of "
          f"{cold.periods}")
    return {"donor_moved": moved, "donor_periods": donor_mgr.periods,
            "warm_periods": warm_periods, "cold_periods": cold_periods,
            "run_periods": cold.periods,
            "donor_params_changed": sum(
                1 for n, v in donor.params.items()
                if abs(v - defaults.get(n, v)) > 1e-12)}


# ----------------------------------------------------------------------
# 4. Attached per-cycle overhead at 10k nodes
# ----------------------------------------------------------------------
def _fragmented_state(n_nodes: int, seed: int = 0) -> ClusterState:
    """~60% of nodes partially busy (same shape as sched_scale_bench)."""
    topo = ClusterTopology(
        n_nodes=n_nodes, gpus_per_node=8, nodes_per_leaf=32,
        leaves_per_spine=4, spines_per_superspine=4, nodes_per_hbd=32)
    state = ClusterState.create(topo)
    rng = np.random.default_rng(seed)
    busy_nodes = rng.random(n_nodes) < 0.6
    busy_count = rng.integers(1, 9, size=n_nodes)
    for node in np.nonzero(busy_nodes)[0]:
        state.gpu_busy[node, :busy_count[node]] = True
    return state


GANG_PODS = 64


def _cycle_stack(n_nodes: int, seed: int):
    """Production-default QSCH stack (incremental snapshots): every
    cycle runs the complete snapshot -> admit -> filter -> score ->
    select -> reserve -> bind pipeline for one 64-pod gang (the §3.4
    hot path)."""
    state = _fragmented_state(n_nodes, seed)
    qm = QuotaManager({"t0": {0: 10**9}})
    rsch = RSCH(state.topology,
                RSCHConfig(train_strategy=Strategy.E_BINPACK))
    qsch = QSCH(qm, rsch, QSCHConfig(policy=QueuePolicy.STRICT_FIFO))
    return state, qsch


def _one_cycle_tuned(state: ClusterState, qsch: QSCH, now: float,
                     mgr: Optional[TuningManager], seq: int):
    """Time one bind cycle plus (when attached) the manager's full
    tick path — wait harvest, controller scans, control-period firing —
    then reset the cluster (untimed)."""
    qsch.submit(Job(uid=1, tenant="t0", gpu_type=0, n_pods=GANG_PODS,
                    gpus_per_pod=8, kind=JobKind.TRAIN))
    t0 = time.perf_counter()
    result = qsch.cycle(state, now)
    if mgr is not None:
        mgr._on_tick(Event(t=now, kind=EventKind.TICK, seq=seq))
    dt = time.perf_counter() - t0
    assert len(result.scheduled) == 1, \
        f"bench gang must bind every cycle: {result}"
    bound = result.scheduled[0]
    picks = tuple((p.node, p.gpu_indices)
                  for p in bound.placement.pods)
    state.release(bound.uid)
    qsch.running.clear()
    qsch.quota.refund(bound)
    return dt, picks


def overhead_gate(seed: int, smoke: bool, n_nodes: int = 10_000) -> Dict:
    repeats = 10 if smoke else 30
    state, qsch = _cycle_stack(n_nodes, seed)
    sim = Simulator(state, qsch, SimConfig(tick_interval=30.0))
    # The escalator's queue scan runs every tick; the huge threshold
    # keeps it from mutating priorities so both arms place identically.
    mgr = TuningManager(
        [NoOpController(),
         StarvationEscalator(wait_threshold_s=1e15)],
        control_period_s=CONTROL_PERIOD_S)
    mgr.attach(sim)
    _one_cycle_tuned(state, qsch, 0.0, None, 0)         # warm caches
    _one_cycle_tuned(state, qsch, 0.0, mgr, 0)
    t_det, t_att = [], []
    for i in range(repeats * 2):
        now = 30.0 * (i + 1)
        dt, picks_det = _one_cycle_tuned(state, qsch, now, None, i)
        t_det.append(dt)
        dt, picks_att = _one_cycle_tuned(state, qsch, now, mgr, i)
        t_att.append(dt)
        assert picks_det == picks_att, \
            "attached arm diverged from the detached placements"
    assert not mgr.space.changes, \
        "overhead arms must not mutate parameters"
    det = float(np.median(t_det))
    att = det + float(np.median(np.subtract(t_att, t_det)))
    overhead = att / det - 1.0
    print(f"--- overhead at {n_nodes} nodes ({GANG_PODS}-pod gang): "
          f"detached {det * 1e3:.2f}ms attached {att * 1e3:.2f}ms "
          f"({overhead:+.1%}, budget 5%); {len(mgr.space)} handles, "
          f"escalator scan live")
    assert overhead <= 0.05, (
        f"attached tuning cost {overhead:+.1%} per cycle at "
        f"{n_nodes} nodes, budget is 5%")
    return {"n_nodes": n_nodes, "gang_pods": GANG_PODS,
            "handles": len(mgr.space),
            "detached_cycle_s": det, "attached_cycle_s": att,
            "overhead": overhead}


# ----------------------------------------------------------------------
def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller configs and repeat counts for CI")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the run-wide benchmark seed")
    args = ap.parse_args(argv)
    seed = args.seed if args.seed is not None else bench_seed()
    summary: Dict = {
        "seed": seed,
        "identity": identity_gate(seed, args.smoke),
        "tuned_vs_static": tuned_vs_static_gate(seed, args.smoke),
        "warm_start": warm_start_gate(seed, args.smoke),
        "overhead": overhead_gate(seed, args.smoke),
    }
    write_bench_json("tuning", summary)
    print(f"tuning bench: all gates passed (attached overhead "
          f"{summary['overhead']['overhead']:+.1%})")


if __name__ == "__main__":
    main()
