"""§3.4 scaling: the million-node scheduling core.

The paper's central engineering claim is that Kant sustains scheduling
efficiency "in clusters ranging from hundreds to tens of thousands of
GPUs".  The hot loop is gang placement: structure-of-arrays cluster
columns, O(groups) tracked-aggregate preselection, subset level-2
scoring over the selected NodeNetGroups only, and the vectorized top-k
slot walk, all on the host's numpy score path.

Measured and gated:

* per-cycle scheduling latency at 1k / 10k / 100k / 1M nodes (64-pod
  gang, realistically fragmented snapshot), host CPU;
* plugin-profile parity: the legacy strategy shim and explicit default
  profiles pick identical nodes, and the profiles cost within 5% per
  cycle up to 10k nodes (interleaved timing);
* **pipelined trace replay**: a multi-day training trace through the
  simulator with ``pipelined_cycles`` off vs on — placements must be
  identical; reports replay throughput, speculation hit/conflict
  stats, and the critical-path per-cycle time (cycle cost minus the
  speculative work that overlaps binding I/O in a real deployment).

Usage::

    PYTHONPATH=src python benchmarks/sched_scale_bench.py [--smoke]

``--smoke`` trims node counts and repeat counts for CI.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):   # `python benchmarks/sched_scale_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from repro.core import (ClusterState, Job, JobKind, QSCH, QSCHConfig,
                        QueuePolicy, QuotaManager, RSCH, RSCHConfig,
                        SimConfig, Simulator, Strategy, default_profiles)
from repro.core.snapshot import FullSnapshotter
from repro.core.topology import ClusterTopology

from benchmarks.common import bench_seed, write_bench_json

GANG_PODS = 64
GPUS_PER_POD = 8


def make_topology(n_nodes: int) -> ClusterTopology:
    """The bench's cluster shape: 8-GPU nodes, 32 per leaf, 4 leaves per
    spine, 4 spines per superspine, 32-node HBDs."""
    return ClusterTopology(
        n_nodes=n_nodes, gpus_per_node=8, nodes_per_leaf=32,
        leaves_per_spine=4, spines_per_superspine=4, nodes_per_hbd=32)


def make_state(n_nodes: int, seed: int = 0) -> ClusterState:
    """A fragmented cluster: ~60% of nodes partially or fully busy.

    Vectorized setup — the old per-node loop took minutes at 1M nodes;
    one broadcast writes the same busy pattern in O(n) numpy.
    """
    topo = make_topology(n_nodes)
    state = ClusterState.create(topo)
    rng = np.random.default_rng(seed)
    busy_nodes = rng.random(n_nodes) < 0.6
    busy_count = rng.integers(1, 9, size=n_nodes)
    state.gpu_busy[:] = ((np.arange(8) < busy_count[:, None])
                         & busy_nodes[:, None])
    return state


def bench_pair(state: ClusterState, repeats: int
               ) -> tuple[float, float, list]:
    """Interleaved best-of-N per-cycle latency (s): legacy-shim RSCH vs
    explicit default profiles, alternating per iteration so load drift
    hits both equally.  Minimum over repeats is the standard
    noise-robust estimator for a deterministic microbenchmark.  Returns
    (t_legacy, t_profiles, the node picks of every cycle of both)."""
    snap = FullSnapshotter().take(state)
    job = Job(uid=1, tenant="bench", gpu_type=0, n_pods=GANG_PODS,
              gpus_per_pod=GPUS_PER_POD, kind=JobKind.TRAIN)
    legacy = RSCH(state.topology,
                  RSCHConfig(train_strategy=Strategy.E_BINPACK))
    explicit = RSCH(state.topology,
                    RSCHConfig(train_strategy=Strategy.E_BINPACK),
                    profiles=default_profiles())
    legacy.schedule(job, snap)                    # warm caches
    explicit.schedule(job, snap)
    t_leg, t_prof, picks = [], [], []
    for _ in range(repeats * 2):
        for rsch, times in ((legacy, t_leg), (explicit, t_prof)):
            t0 = time.perf_counter()
            result = rsch.schedule(job, snap)
            times.append(time.perf_counter() - t0)
            assert result.placement is not None, (
                "bench job must be placeable")
            picks.append([(p.node, p.gpu_indices, p.nic)
                          for p in result.placement.pods])
    return float(np.min(t_leg)), float(np.min(t_prof)), picks


# ----------------------------------------------------------------------
# Pipelined multi-day trace replay
# ----------------------------------------------------------------------
def _placement_key(jobs):
    out = []
    for j in sorted(jobs, key=lambda j: j.uid):
        if j.placement is None:
            out.append((j.uid, j.start_time, None))
        else:
            out.append((j.uid, j.start_time,
                        tuple((p.node, tuple(p.gpu_indices))
                              for p in j.placement.pods)))
    return out


def trace_replay(n_nodes: int, n_jobs: int, seed: int) -> dict:
    """Replay a multi-day contended training trace with pipelining off
    vs on: placements must match; report throughput + pipeline stats."""
    rng = np.random.default_rng(seed)
    # ~2 simulated days of arrivals, enough width to keep a backlog.
    jobs = [Job(uid=i, tenant=f"t{i % 4}", gpu_type=0,
                n_pods=int(rng.integers(1, 9)),
                gpus_per_pod=int(rng.choice([4, 8])),
                duration=float(rng.integers(1800, 40000)),
                submit_time=float(rng.integers(0, 172800)),
                priority=int(rng.integers(0, 3)),
                kind=JobKind.TRAIN) for i in range(n_jobs)]

    def replay(pipelined):
        topo = make_topology(n_nodes)
        state = ClusterState.create(topo)
        quota = QuotaManager({f"t{i}": {0: 10 ** 9} for i in range(4)})
        rsch = RSCH(topo,
                    RSCHConfig(train_strategy=Strategy.E_BINPACK))
        qsch = QSCH(quota, rsch, QSCHConfig(policy=QueuePolicy.BACKFILL))
        sim = Simulator(state, qsch,
                        SimConfig(pipelined_cycles=pipelined))
        t0 = time.perf_counter()
        res = sim.run([Job(uid=j.uid, tenant=j.tenant, gpu_type=0,
                           n_pods=j.n_pods, gpus_per_pod=j.gpus_per_pod,
                           duration=j.duration,
                           submit_time=j.submit_time,
                           priority=j.priority, kind=j.kind)
                       for j in jobs])
        wall = time.perf_counter() - t0
        return _placement_key(res.jobs), res, wall

    base_key, base_res, base_wall = replay(False)
    pipe_key, pipe_res, pipe_wall = replay(True)
    assert base_key == pipe_key, (
        "pipelined replay diverged from sequential replay")
    stats = pipe_res.pipeline
    cycles = max(1, pipe_res.cycles)
    per_cycle = pipe_wall / cycles
    # Speculative work overlaps binding I/O in a pipelined deployment;
    # what remains on the critical path is the cycle cost minus it.
    critical = max(0.0, pipe_wall - stats["spec_seconds"]) / cycles
    return {
        "n_nodes": n_nodes, "n_jobs": len(jobs),
        "cycles": pipe_res.cycles,
        "baseline_wall_s": base_wall,
        "pipelined_wall_s": pipe_wall,
        "cycles_per_s": cycles / pipe_wall,
        "jobs_per_s": len(jobs) / pipe_wall,
        "per_cycle_ms": per_cycle * 1e3,
        "critical_path_per_cycle_ms": critical * 1e3,
        "speculated": stats["speculated"], "hits": stats["hits"],
        "conflicts": stats["conflicts"], "misses": stats["misses"],
        "errors": stats["errors"],
        "spec_seconds": stats["spec_seconds"],
    }


def run_bench(smoke: bool = False) -> dict:
    seed = bench_seed()
    if smoke:
        sizes = (1000, 10_000)
        repeats = 9
        replay_nodes, replay_jobs = 128, 300
    else:
        sizes = (1000, 10_000, 100_000, 1_000_000)
        repeats = 15
        replay_nodes, replay_jobs = 256, 800

    rows = {}
    print(f"{'nodes':>8s} {'per-cycle':>12s} {'profiles':>12s} "
          f"{'overhead':>9s} {'pods/s':>9s}")
    for n in sizes:
        state = make_state(n, seed=seed)
        t_shim, t_prof, picks = bench_pair(state, repeats)
        assert all(p == picks[0] for p in picks), (
            f"profile-built RSCH diverged from the legacy shim at {n} "
            f"nodes")
        row = {"per_cycle_s": t_shim, "profile_s": t_prof,
               "profile_overhead": t_prof / t_shim - 1.0,
               "placements_per_s": GANG_PODS / t_shim}
        if n <= 10_000:
            # Plugin-framework parity (api_redesign acceptance gate).
            # 100us absolute floor: the path is fast enough at 1k nodes
            # that a relative-only bound measures timer jitter.
            assert t_prof <= max(t_shim * 1.05, t_shim + 100e-6), (
                f"profile engine must stay within 5% of the shim path "
                f"at {n} nodes, got {row['profile_overhead']:+.1%}")
        print(f"{n:8d} {t_shim * 1e3:10.2f}ms {t_prof * 1e3:10.2f}ms "
              f"{row['profile_overhead']:+8.1%} "
              f"{row['placements_per_s']:9.0f}")
        rows[n] = row

    replay = trace_replay(replay_nodes, replay_jobs, seed)
    hit_pool = max(1, replay["hits"] + replay["misses"])
    print(f"[ok] pipelined trace replay ({replay['n_nodes']} nodes, "
          f"{replay['n_jobs']} jobs, {replay['cycles']} cycles): "
          f"placements identical; {replay['cycles_per_s']:.0f} "
          f"cycles/s; per-cycle {replay['per_cycle_ms']:.2f}ms -> "
          f"critical path {replay['critical_path_per_cycle_ms']:.2f}ms; "
          f"spec hit rate {replay['hits']}/{hit_pool}, "
          f"{replay['conflicts']} conflicts, {replay['errors']} errors")

    write_bench_json("sched_scale", {
        "per_cycle": {str(k): v for k, v in rows.items()},
        "trace_replay": replay, "smoke": smoke, "seed": seed})
    return rows


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="trimmed sizes/repeats for CI")
    args = parser.parse_args(argv)
    return run_bench(smoke=args.smoke)


if __name__ == "__main__":
    main()
    sys.exit(0)
