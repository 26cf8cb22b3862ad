"""Faults planted under the timed path, each of which the check has to
catch (``tests/bench/test_bench_faults.py``).  Each is a ``plant``
for ``bench.run.run_once``: it changes the system under test before
set-up and leaves the harness as it is.

* ``state_unchanged``: binding a job records it but leaves the
  cluster's GPUs as they were;
* ``half_the_nodes``: the score pass sees only half of the node table,
  every other node masked out;
* ``altered_answer``: RSCH's placement of a one-pod job is changed
  where it is made: the pod takes the last free GPUs of its node
  instead of the first.

The fourth fault of the list, an exchange between chips left out, has
nothing to break here: every cell runs on one chip and no path of the
scheduler crosses chips.
"""

from __future__ import annotations


def state_unchanged(state, rsch, qsch, calls) -> None:
    def allocate(job, placement):
        state.allocations[job.uid] = placement

    state.allocate = allocate


def half_the_nodes(state, rsch, qsch, calls) -> None:
    impl = calls.impl

    def scores_and_slots(free, used, mask, group_load, topo_pref, **kw):
        import numpy as np
        mask = np.array(mask)
        mask[1::2] = 0
        return impl(free, used, mask, group_load, topo_pref, **kw)

    calls.impl = scores_and_slots


def altered_answer(state, rsch, qsch, calls) -> None:
    from repro.core import Placement, PodPlacement
    schedule = rsch.schedule

    def altered(job, snap, ctx=None):
        res = schedule(job, snap, ctx)
        if res.placement is None or len(res.placement.pods) != 1:
            return res
        first = res.placement.pods[0]
        free = [g for g in range(state.gpus_per_node)
                if not snap.gpu_busy[first.node, g]]
        gpus = tuple(free[-len(first.gpu_indices):])
        if sorted(gpus) != sorted(first.gpu_indices):
            res.placement = Placement(pods=[PodPlacement(
                node=first.node, gpu_indices=gpus, nic=first.nic)])
        return res

    rsch.schedule = altered
