"""A configuration's cluster: its shape and its background occupancy.

Both come from the configuration file and the seed alone, so the
program under test and the plain reference start from the same arrays
without one taking them from the other.  The background generator is a
copy of ``make_state`` in ``benchmarks/sched_scale_bench.py``: a share
of the nodes is busy, each with a drawn number of busy GPUs taken from
the lowest slots.
"""

from __future__ import annotations

import numpy as np


def seed_seq(seed: int, *salt: int) -> np.random.Generator:
    """A generator for one use of ``seed``: any whole number, however
    large, and a salt that keeps the uses apart."""
    return np.random.default_rng([int(seed) % 2 ** 64, *salt])


def leaf_ids(config: dict) -> np.ndarray:
    return np.arange(config["nodes"], dtype=np.int64) // \
        config["nodes_per_leaf"]


def spine_of_leaf(config: dict) -> np.ndarray:
    n_leaves = -(-config["nodes"] // config["nodes_per_leaf"])
    return np.arange(n_leaves, dtype=np.int64) // config["leaves_per_spine"]


def background_busy(config: dict, seed: int) -> np.ndarray:
    """(nodes, gpus_per_node) bool: the GPUs busy before the window."""
    n, g = config["nodes"], config["gpus_per_node"]
    bg = config["background"]
    rng = seed_seq(seed, 0x6267)               # "bg"
    busy_nodes = rng.random(n) < bg["busy_node_share"]
    weights = np.asarray(bg["busy_count_weights"], dtype=np.float64)
    if weights.shape != (g,):
        raise ValueError("busy_count_weights needs one weight for each "
                         "count 1..gpus_per_node")
    cum = np.cumsum(weights / weights.sum())
    counts = 1 + np.searchsorted(cum, rng.random(n), side="right")
    counts = np.minimum(counts, g)
    return (np.arange(g) < counts[:, None]) & busy_nodes[:, None]


def build_program(config: dict, busy: np.ndarray, backend: str):
    """The system under test, configured as the file states: the
    cluster state with its background, RSCH on ``backend`` with the
    default profiles, and QSCH with Backfill and the file's quotas."""
    from repro.core import (ClusterState, QSCH, QSCHConfig, QueuePolicy,
                            QuotaManager, RSCH, RSCHConfig,
                            default_profiles)
    from repro.core.topology import ClusterTopology

    if config["queue_policy"] != "backfill" or \
            config["profile"] != "e-binpack":
        raise ValueError("only Backfill with the default E-Binpack "
                         "profile is wired here")
    topo = ClusterTopology(
        n_nodes=config["nodes"], gpus_per_node=config["gpus_per_node"],
        nodes_per_leaf=config["nodes_per_leaf"],
        leaves_per_spine=config["leaves_per_spine"],
        spines_per_superspine=config["spines_per_superspine"],
        nodes_per_hbd=config["nodes_per_hbd"],
        nvlink_island=config["nvlink_island"])
    state = ClusterState.create(topo)
    state.gpu_busy[:] = busy
    rsch = RSCH(topo, RSCHConfig(score_backend=backend),
                profiles=default_profiles(
                    colocate=config["colocate_bonus"]))
    quota = QuotaManager({t: {config["gpu_type"]: q}
                          for t, q in config["tenants"].items()})
    qsch = QSCH(quota, rsch, QSCHConfig(
        policy=QueuePolicy.BACKFILL,
        backfill_head_timeout=config["backfill_head_timeout_s"]))
    return state, rsch, qsch
