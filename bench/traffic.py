"""The one job generator every traffic mix goes through.

A mix is a data file (``bench/traffic/``): the job population, how jobs
arrive, and how long they run.  Jobs come in blocks of ``block_jobs``;
every block holds each size as many times as the population states, in
an order drawn from the seed.  Run times are exponential with the
stated mean times the size's ``duration_scale``.

Arrival kinds:

* ``"open_loop"``: a Poisson process at ``rate_per_s``: independent
  exponential gaps drawn from the seed.  Jobs are due at their arrival
  times, in seconds from the window's start, whatever the scheduler
  does;
* ``"backlog"``: ``jobs_per_cycle`` jobs join the queue before each
  cycle, stamped with the cycle's simulated time.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from .cluster import seed_seq


@dataclasses.dataclass
class JobSpec:
    uid: int
    n_pods: int
    gpus_per_pod: int
    due: float          # seconds from the window's start (open loop)
    duration: float     # seconds of simulated time once placed

    @property
    def n_gpus(self) -> int:
        return self.n_pods * self.gpus_per_pod


def pod_shape(n_gpus: int, gpus_per_node: int) -> tuple:
    """(n_pods, gpus_per_pod): jobs larger than a node use whole-node
    pods, smaller ones one pod."""
    if n_gpus <= gpus_per_node:
        return 1, n_gpus
    if n_gpus % gpus_per_node:
        raise ValueError(f"{n_gpus} GPUs is not a whole number of nodes")
    return n_gpus // gpus_per_node, gpus_per_node


def pod_sizes(traffic: dict, gpus_per_node: int) -> List[int]:
    """The distinct ``gpus_per_pod`` the mix asks for (what to warm)."""
    return sorted({pod_shape(p["gpus"], gpus_per_node)[1]
                   for p in traffic["population"] if p["per_block"]})


class JobStream:
    """Jobs of a mix in arrival order, made block by block on demand."""

    def __init__(self, traffic: dict, gpus_per_node: int, seed: int):
        self.traffic = traffic
        self.gpus_per_node = gpus_per_node
        self.seed = seed
        pop = traffic["population"]
        self.block = int(traffic["block_jobs"])
        if sum(p["per_block"] for p in pop) != self.block:
            raise ValueError("population counts must add up to block_jobs")
        self.jobs: List[JobSpec] = []
        self._t = 0.0

    def _make_block(self) -> None:
        tr = self.traffic
        b = len(self.jobs) // self.block
        rng = seed_seq(self.seed, 0x6a6f62, b)      # "job"
        pop = [p for p in tr["population"] if p["per_block"]]
        gpus = np.repeat([p["gpus"] for p in pop],
                         [p["per_block"] for p in pop])
        scale = np.repeat([p["duration_scale"] for p in pop],
                          [p["per_block"] for p in pop])
        order = rng.permutation(self.block)
        gpus, scale = gpus[order], scale[order]
        dur = rng.exponential(tr["mean_duration_s"], self.block) * scale
        if tr["arrival"] == "open_loop":
            gaps = rng.exponential(1.0 / tr["rate_per_s"], self.block)
            due = self._t + np.cumsum(gaps)
            self._t = float(due[-1])
        elif tr["arrival"] == "backlog":
            due = np.zeros(self.block)
        else:
            raise ValueError(f"unknown arrival kind {tr['arrival']!r}")
        base = len(self.jobs)
        for i in range(self.block):
            n_pods, per_pod = pod_shape(int(gpus[i]), self.gpus_per_node)
            self.jobs.append(JobSpec(uid=base + i, n_pods=n_pods,
                                     gpus_per_pod=per_pod,
                                     due=float(due[i]),
                                     duration=float(dur[i])))

    def __getitem__(self, i: int) -> JobSpec:
        while i >= len(self.jobs):
            self._make_block()
        return self.jobs[i]
