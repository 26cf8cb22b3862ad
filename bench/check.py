"""Decide ``correct``: the window's work, compared with the reference.

Three numbers, each a count of things that differ, each with the limit
0 (an exact comparison; ``PERF.md`` gives the readings):

* ``score_calls``: sampled score calls in which any node differs: its
  score from the kernel in any bit, or its pod-slot count, from the
  reference's float32 pass over the same inputs; or one of those
  inputs (free and used GPUs, pool mask, group load, anchor rank) from
  what the reference derives itself for that decision;
* ``decisions``: decisions that differ from the reference's, position
  by position within each cycle (which job, placed or not, on which
  nodes and GPU indices), plus any eviction, which the reference never
  makes;
* ``gpu_state``: GPUs whose busy bit at the end differs from the
  reference's.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .cluster import background_busy
from .reference import Reference, fused_scores, pod_slots

LIMITS = {"score_calls": 0, "decisions": 0, "gpu_state": 0}


def _bits(x) -> np.ndarray:
    return np.asarray(x, np.float32).view(np.uint32)


def outputs_differ(config: dict, c) -> bool:
    """The kernel's output against the reference on the call's inputs."""
    want = fused_scores(c.free, c.used, c.mask, c.group_load, c.topo_pref,
                        c.request, config["gpus_per_node"],
                        config["score_weights"])
    return bool(np.any(_bits(want) != _bits(c.scores))
                or np.any(pod_slots(c.free, c.mask, c.request)
                          != np.asarray(c.slots)))


def replay(config: dict, traffic: dict, seed: int, win, captures: dict,
           final_busy: np.ndarray, pod_sizes) -> Dict[str, int]:
    """Replay the window's log through the reference."""
    ref = Reference(config, background_busy(config, seed), pod_sizes)
    tenant, prio = traffic["tenant"], int(traffic["priority"])
    decisions = win.preemptions
    bad_calls = set(i for i, c in captures.items()
                    if outputs_differ(config, c))
    for ev in win.events:
        if ev[0] == "submit":
            ref.submit(win.specs[ev[1]], ev[2], tenant, prio)
        elif ev[0] == "end":
            ref.release(ev[1])
        else:
            cyc = win.cycles[ev[1]]
            probes, unseen = {}, set()
            for uid, _, call in cyc.attempts:
                cap = captures.get(call)
                if cap is None:
                    continue
                unseen.add(call)

                def probe(groups, cap=cap):
                    unseen.discard(cap.index)
                    mask, load, anchor = ref.level2_inputs(groups)
                    if (np.any(cap.free != ref.free)
                            or np.any(cap.used != ref.G - ref.free)
                            or np.any((cap.mask != 0) != (mask != 0))
                            or np.any(_bits(cap.group_load) != _bits(load))
                            or np.any(_bits(cap.topo_pref)
                                      != _bits(anchor))):
                        bad_calls.add(cap.index)
                probes[uid] = probe
            mine = ref.cycle(probes)
            bad_calls |= unseen       # the reference made no such call
            got = [(uid, pods) for uid, pods, _ in cyc.attempts]
            decisions += sum(a != b for a, b in zip(got, mine))
            decisions += abs(len(got) - len(mine))
    return {"score_calls": len(bad_calls), "decisions": decisions,
            "gpu_state": int(np.count_nonzero(ref.busy != final_busy))}


def check(config: dict, traffic: dict, seed: int, win, captures: dict,
          final_busy: np.ndarray, pod_sizes) -> tuple:
    """Returns ({name: (value, limit)}, seconds the reference took)."""
    t0 = time.perf_counter()
    values = replay(config, traffic, seed, win, captures, final_busy,
                    pod_sizes)
    return ({k: (values[k], LIMITS[k]) for k in LIMITS},
            time.perf_counter() - t0)
