"""Observability benchmark: zero-cost detachment, complete traces.

Two gates, matching the telemetry subsystem's acceptance criteria:

1. **Byte-identity** — attaching a full :class:`repro.obs.Telemetry`
   (registry + tracing + audit) must not perturb the simulation: across
   a policy x strategy matrix, placements, metric reports and the raw
   sample series are identical to the untelemetered run.  Detached,
   every ``obs`` hook is a single ``is None`` branch.
2. **Trace completeness** — on a seeded elastic run with node failures,
   the emitted Chrome-trace has a span/instant for every lifecycle bus
   event: one ``job-<uid>`` B per SUBMIT, an E at every authoritative
   END, a ``NODE_FAIL`` instant per failure event and a ``reshape``
   instant per voluntary reshape, with every B/E lane balanced.

Writes ``BENCH_obs.json`` plus a sample Perfetto-loadable trace
``BENCH_obs_trace.json`` (both uploaded as CI artifacts).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

if __package__ in (None, ""):   # `python benchmarks/obs_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import (bench_seed, clone_jobs, scale_topology,
                               write_bench_json)  # noqa: E402
from repro.core import (CheckpointModel, ClusterState, DynamicsConfig,
                        ElasticManager, Job, JobState,
                        NodeFailureInjector, QSCH, QSCHConfig,
                        QueuePolicy, QuotaManager, RSCH, RSCHConfig,
                        SimConfig, Simulator, SimResult, Strategy,
                        scaling_artifacts, spec_from_artifacts,
                        training_trace)  # noqa: E402
from repro.obs import PID_JOBS, Telemetry  # noqa: E402


def run_sim(jobs: Sequence[Job], *, policy=QueuePolicy.BACKFILL,
            strategy=Strategy.E_BINPACK, telemetry: Optional[Telemetry]
            = None, horizon: Optional[float] = None,
            dynamics: Optional[DynamicsConfig] = None,
            elastic: bool = False, n_gpus: int = 512) -> SimResult:
    topo = scale_topology(n_gpus=n_gpus)
    state = ClusterState.create(topo)
    qm = QuotaManager({"t0": {0: 10**6}})
    rsch = RSCH(topo, RSCHConfig(train_strategy=strategy))
    qsch = QSCH(qm, rsch, QSCHConfig(policy=policy),
                elastic=ElasticManager() if elastic else None)
    sim = Simulator(state, qsch,
                    SimConfig(tick_interval=30.0, sample_interval=300.0,
                              binding_latency=45.0, horizon=horizon,
                              dynamics=dynamics))
    if telemetry is not None:
        telemetry.attach(sim)
    return sim.run(clone_jobs(jobs))


def placement_fingerprint(result: SimResult) -> List:
    return [(j.uid, j.start_time, j.end_time,
             tuple((p.node, p.gpu_indices)
                   for p in (j.placement.pods if j.placement else ())))
            for j in result.jobs]


def sample_series(result: SimResult) -> List[Dict]:
    return [dataclasses.asdict(s) for s in result.metrics.samples]


# ----------------------------------------------------------------------
# 1. Byte-identity: attached telemetry must not perturb the simulation
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
    families = 0
    for policy, strategy in configs:
        base = run_sim(jobs, policy=policy, strategy=strategy)
        tel = Telemetry()
        inst = run_sim(jobs, policy=policy, strategy=strategy,
                       telemetry=tel)
        tag = f"{policy.name} x {strategy.name}"
        assert placement_fingerprint(base) == placement_fingerprint(
            inst), f"telemetry perturbed placements: {tag}"
        assert base.metrics.report() == inst.metrics.report(), \
            f"telemetry perturbed the metric report: {tag}"
        assert sample_series(base) == sample_series(inst), \
            f"telemetry perturbed the raw sample series: {tag}"
        families = len(tel.registry.names())
        assert families > 0, "attached run registered no metric families"
        assert tel.audit.bound(), f"no decisions audited: {tag}"
    print(f"--- identity: {len(configs)} policy x strategy configs "
          f"byte-identical with full telemetry attached "
          f"({families} metric families)")
    return {"configs_checked": len(configs),
            "metric_families": families}


# ----------------------------------------------------------------------
# 2. Trace completeness on a failing, reshaping cluster
# ----------------------------------------------------------------------
def _dynamic_workload(seed: int, smoke: bool) -> List[Job]:
    """Rigid fragmenters + elastic 128-GPU gangs on 512 GPUs: under
    failures the gangs shrink/grow, producing reshape bus traffic."""
    rng = np.random.default_rng(seed)
    jobs: List[Job] = []
    n_small = 40 if smoke else 80
    window = (4.0 if smoke else 8.0) * 3600.0
    for i in range(n_small):
        n_gpus = int(rng.choice([8, 16, 32], p=[.45, .35, .2]))
        jobs.append(Job(uid=i, tenant="t0", gpu_type=0,
                        n_pods=n_gpus // 8, gpus_per_pod=8,
                        submit_time=float(rng.uniform(0.0, window)),
                        duration=float(rng.uniform(1.0, 2.5)) * 3600.0))
    spec = spec_from_artifacts(
        scaling_artifacts("obs-train", "large", [32, 64, 128],
                          alpha=0.85))
    ideal = spec.ideal()
    for k in range(6 if smoke else 10):
        jobs.append(Job(uid=10_000 + k, tenant="t0", gpu_type=0,
                        n_pods=ideal.n_pods,
                        gpus_per_pod=ideal.gpus_per_pod,
                        submit_time=float(rng.uniform(0.0, 0.6 * window)),
                        duration=float(rng.uniform(2.0, 3.5)) * 3600.0,
                        elastic=spec))
    return jobs


def trace_gate(seed: int, smoke: bool) -> Dict:
    jobs = _dynamic_workload(seed, smoke)
    horizon = (10 if smoke else 18) * 3600.0
    dynamics = DynamicsConfig(
        plugins=[NodeFailureInjector(mtbf_s=4 * 3600.0, repair_s=1200.0,
                                     shape=1.2)],
        seed=seed,
        recovery=CheckpointModel(interval_s=600.0,
                                 restart_overhead_s=180.0))
    tel = Telemetry()
    result = run_sim(jobs, telemetry=tel, horizon=horizon,
                     dynamics=dynamics, elastic=True)
    events = tel.tracer.to_json()["traceEvents"]

    # Every SUBMIT opened a job span; lanes are balanced after finalize.
    begins = {e["name"] for e in events
              if e["ph"] == "B" and e["pid"] == PID_JOBS}
    submitted = {f"job-{j.uid}" for j in result.jobs}
    assert begins == submitted, (
        f"job spans != submitted jobs: {len(begins)} spans for "
        f"{len(submitted)} SUBMITs")
    lanes: Dict[tuple, int] = {}
    for e in events:
        if e["ph"] == "B":
            lanes[(e["pid"], e["tid"])] = lanes.get(
                (e["pid"], e["tid"]), 0) + 1
        elif e["ph"] == "E":
            lanes[(e["pid"], e["tid"])] = lanes.get(
                (e["pid"], e["tid"]), 0) - 1
    assert all(v == 0 for v in lanes.values()), \
        f"unbalanced B/E lanes: {lanes}"

    # Every authoritative END has an E at exactly the job's end time
    # (close_all-injected Es are tagged and excluded).
    ended = {e["name"]: e["ts"] for e in events
             if e["ph"] == "E" and e["pid"] == PID_JOBS
             and not (e.get("args") or {}).get("closed_at_finalize")}
    completed = [j for j in result.jobs if j.state is JobState.COMPLETED]
    assert len(ended) == len(completed), (
        f"{len(ended)} end spans for {len(completed)} completed jobs")
    for j in completed:
        assert abs(ended[f"job-{j.uid}"] - j.end_time * 1e6) < 1.0, \
            f"job {j.uid} E span not at its END time"

    # Every NODE_FAIL bus event and every voluntary reshape left a mark.
    n_fail_inst = sum(1 for e in events
                      if e["ph"] == "i" and e["name"] == "NODE_FAIL")
    n_fail_bus = tel.event_counts.get("NODE_FAIL", 0)
    assert n_fail_bus > 0, "scenario produced no node failures"
    assert n_fail_inst == n_fail_bus, (
        f"{n_fail_inst} NODE_FAIL instants for {n_fail_bus} bus events")
    reshape_inst = sum(1 for e in events
                       if e["ph"] == "i" and e["name"] == "reshape")
    reshapes = result.metrics.reshapes
    assert reshapes > 0, "scenario produced no reshapes"
    assert reshape_inst == reshapes, (
        f"{reshape_inst} reshape instants for {reshapes} reshapes")

    trace_path = tel.save_trace(os.path.abspath("BENCH_obs_trace.json"))
    print(f"--- trace: {len(events)} events cover {len(submitted)} "
          f"SUBMITs, {len(completed)} ENDs, {n_fail_bus} NODE_FAILs, "
          f"{reshapes} reshapes; lanes balanced")
    print(f"    [trace] {trace_path}")
    return {"trace_events": len(events), "jobs": len(submitted),
            "completed": len(completed), "node_fails": n_fail_bus,
            "reshapes": reshapes, "trace_path": trace_path}


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
        "trace": trace_gate(seed, args.smoke),
    }
    write_bench_json("obs", summary)
    print("obs bench: all gates passed")


if __name__ == "__main__":
    main()
