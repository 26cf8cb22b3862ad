"""Reference implementations the tests hold the scheduler to."""

from typing import List, Optional

import numpy as np

from repro.core import RSCH
from repro.core.scoring import NEG_INF, node_scores_np


class SequentialRSCH(RSCH):
    """RSCH whose Level 2 is the O(n_pods × n_nodes) per-pod loop that
    batched gang placement (§3.4) replaces: over the full width, one
    numpy filter+score pass and its argmax per pod, with the co-location
    bonus added once per pod the job already holds on a node.  The
    batched slot walk must pick the same nodes in the same order."""

    def _use_subset(self, default_pool, capture) -> bool:
        return False

    def _select_nodes_full(self, job, snap, mask, gload_nodes, topo_pref,
                           weights, colocate, slots=None, extra=None,
                           score_out=None) -> Optional[List[int]]:
        free = snap.free_gpus.copy()        # mutated as pods are placed
        nodes: List[int] = []
        for _ in range(job.n_pods):
            scores = node_scores_np(
                free, snap.used_gpus, mask, gload_nodes, topo_pref,
                job.gpus_per_pod, self.topology.gpus_per_node, weights)
            if extra is not None:
                scores = np.where(scores > NEG_INF, scores + extra, scores)
            for n in nodes:
                if scores[n] > NEG_INF:
                    scores[n] += colocate
            node = int(np.argmax(scores))
            if scores[node] <= NEG_INF:
                return None
            free[node] -= job.gpus_per_pod
            nodes.append(node)
        return nodes
