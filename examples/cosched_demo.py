"""Co-scheduling demo: Kant placements -> placement-aware roofline.

The paper's JTTED metric (§4.5) argues that a placement spanning more
NodeNetGroups costs training time.  Because this framework owns both the
scheduler *and* the workloads, we close the loop (beyond-paper feature,
``repro.launch.cosched``): a Kant placement is scored by its deviation
ratios and the job's roofline collective term is rescaled by the
placement's effective bisection bandwidth.

The demo schedules the same 64-GPU training gang job twice — once with
E-Binpack (consolidates into one LeafGroup) and once with Spread (leaks
across groups) — on a pre-fragmented cluster, then prices both placements
with the dry-run roofline terms of a real (arch x shape) lowering.

Usage::

    PYTHONPATH=src python examples/cosched_demo.py
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

from repro.core import (ClusterState, Job, JobKind, RSCH, ProfileSet)
from repro.core.framework import (ebinpack_pass, make_profile,
                                  single_pass_plan, spread_pass)
from repro.core.snapshot import FullSnapshotter
from repro.core.topology import ClusterTopology
from repro.launch.cosched import (estimated_step_time, job_mesh_shape,
                                  placement_quality)

DRYRUN_GLOB = "experiments/dryrun/glm4-9b__train_4k__16x16__*.json"
FALLBACK_TERMS = {"compute": 3.0e-1, "memory": 9.0e-1,
                  "collective": 2.0e-1}     # glm4-9b/train_4k magnitudes


def load_terms():
    hits = sorted(glob.glob(DRYRUN_GLOB))
    if not hits:
        print(f"  (no dry-run artifact under {os.path.dirname(DRYRUN_GLOB)}"
              " — using fallback terms; run `python -m repro.launch.dryrun"
              " --arch glm4-9b --shape train_4k` for real numbers)")
        return FALLBACK_TERMS, "fallback"
    with open(hits[0]) as f:
        r = json.load(f)
    return ({"compute": r["compute_term_s"], "memory": r["memory_term_s"],
             "collective": r["collective_term_s"]}, os.path.basename(hits[0]))


def uniform_profiles(name: str, pass_) -> ProfileSet:
    """One placement pass for every workload class (framework API)."""
    p = make_profile(name, single_pass_plan(pass_))
    return ProfileSet(train=p, inference=p, best_effort=p)


SPREAD_PROFILES = uniform_profiles("bg-spread", spread_pass())
EBINPACK_PROFILES = uniform_profiles("bg-e-binpack",
                                     ebinpack_pass(colocate=2.0))


def fragment(state: ClusterState, topo: ClusterTopology,
             rng: np.random.Generator, profiles: ProfileSet,
             n_jobs: int = 48) -> None:
    """Place small background jobs with the profile under test.

    Spread scatters them across every LeafGroup; E-Binpack consolidates
    them into few groups, *reserving whole groups* for the large job that
    arrives next (§3.3.3 LeafGroup-level E-Binpack)."""
    rsch = RSCH(topo, profiles=profiles)
    for uid in range(10_000, 10_000 + n_jobs):
        j = Job(uid=uid, tenant="bg", gpu_type=0, n_pods=1,
                gpus_per_pod=int(rng.choice([2, 4])), kind=JobKind.TRAIN,
                gang=True, submit_time=0.0, duration=1e9)
        res = rsch.schedule(j, FullSnapshotter().take(state))
        if res.placement is not None:
            state.allocate(j, res.placement)


def place_and_price(bg_name: str, bg_profiles: ProfileSet, topo, terms,
                    seed: int = 3):
    """Fill the cluster with small jobs under ``bg_profiles``, then place
    one 64-GPU gang training job and price its placement."""
    state = ClusterState.create(topo)
    fragment(state, topo, np.random.default_rng(seed), bg_profiles)
    job = Job(uid=1, tenant="llm", gpu_type=0, n_pods=8, gpus_per_pod=8,
              kind=JobKind.TRAIN, gang=True, submit_time=0.0,
              duration=3600.0)
    rsch = RSCH(topo, profiles=EBINPACK_PROFILES)
    res = rsch.schedule(job, FullSnapshotter().take(state))
    if res.placement is None:
        print(f"  bg={bg_name:10s}: 64-GPU job does not fit "
              f"({res.reason})")
        return None
    q = placement_quality(res.placement, topo, job.n_gpus)
    t = estimated_step_time(terms, q)
    from repro.launch.cosched import ICI_BW, effective_collective_bw
    coll = terms["collective"] * ICI_BW / effective_collective_bw(q)
    print(f"  bg={bg_name:10s}: nodes={q.n_nodes} "
          f"groups={q.n_groups} node_dev={q.node_dev:.2f} "
          f"group_dev={q.group_dev:.2f} "
          f"cross_group={q.cross_group_fraction:.2f} "
          f"-> collective {coll:.2f}s, est step {t*1e3:.0f} ms")
    return t, coll


def main():
    terms, src = load_terms()
    print(f"roofline terms from {src}:")
    print(f"  compute {terms['compute']:.3e}s  memory "
          f"{terms['memory']:.3e}s  collective {terms['collective']:.3e}s")
    data, model = job_mesh_shape(64)
    print(f"64-GPU job mesh factorization: data={data} x model={model}\n")

    topo = ClusterTopology(n_nodes=64, gpus_per_node=8, nodes_per_leaf=8,
                           leaves_per_spine=4, spines_per_superspine=2,
                           nodes_per_hbd=8, nvlink_island=8, numa_split=4)
    print("one 64-GPU (8 pods x 8) gang training job arriving on a "
          "512-GPU cluster\nalready running 48 small jobs placed with the "
          "strategy under test:")
    r_spread = place_and_price("SPREAD", SPREAD_PROFILES, topo, terms)
    r_ebp = place_and_price("E_BINPACK", EBINPACK_PROFILES, topo, terms)

    if r_spread and r_ebp:
        (t_s, c_s), (t_e, c_e) = r_spread, r_ebp
        print(f"\nE-Binpack background packing cuts the large job's "
              f"collective term {c_s / c_e:.2f}x "
              f"({c_s:.2f}s -> {c_e:.2f}s); step estimate "
              f"{t_s*1e3:.0f} -> {t_e*1e3:.0f} ms "
              f"(memory-bound here, so the win shows once the memory "
              f"term is optimized — see EXPERIMENTS.md §Perf)")
        assert c_e <= c_s + 1e-12
        assert t_e <= t_s + 1e-12
    print("cosched_demo complete")


if __name__ == "__main__":
    main()
