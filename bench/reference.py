"""Plain reference of the scheduling decisions a configuration states.

It imports nothing of the program.  From the configuration file, the
background occupancy (``bench.cluster``) and the job specs
(``bench.traffic``) it replays the window's log (which jobs arrived
before each cycle, which ended) and makes every decision itself:

* QSCH, Backfill: each cycle tries the pending jobs once, in the order
  (priority desc, submit time, size, uid); a job is decided as placed
  or as found not to fit (quota, too few free slots, no group set, no
  placement);
* RSCH level 1, E-Binpack's NodeNetGroup choice: the busiest group
  (most used GPUs, then fewest free, then lowest index) that holds the
  whole gang, or else a greedy cover from the group with the most pod
  slots, same-spine groups first;
* RSCH level 2: the fused node score, in float32, term by term
  ``w_used*used/G + w_fit*[free == request] + w_group*group_load +
  w_topo*anchor_rank``, over the chosen groups' healthy nodes;
* slot selection: pod by pod, the node whose next slot is worth most,
  ``score - w_fit*[free == request] + colocate*k + w_fit*[free -
  k*request == request]`` for its k-th slot, ties to the lowest node
  index (exact in float64);
* device selection: the first NVLink island with room for the pod,
  else the free GPUs in (island, slot) order.

Every cluster here is healthy and of one GPU type, so the pool is every
node.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .cluster import leaf_ids, spine_of_leaf


def fused_scores(free, used, mask, group_load, anchor, request: int,
                 gpus_per_node: int, w: dict, dtype=np.float32
                 ) -> np.ndarray:
    """The fused filter+score in the order the configuration states it,
    each operation rounded to ``dtype`` (float32, as the configuration
    states; the control passes bfloat16), returned as float32 with
    ``-inf`` (float32 min) at nodes that cannot take a pod."""
    t = np.dtype(dtype).type
    free_t = np.asarray(free).astype(dtype)
    exact = (np.asarray(free) == request).astype(dtype)
    score = (t(w["used"]) * np.asarray(used).astype(dtype)
             * t(1.0 / gpus_per_node)
             + t(w["fit"]) * exact
             + t(w["group"]) * np.asarray(group_load).astype(dtype)
             + t(w["topo"]) * np.asarray(anchor).astype(dtype))
    valid = (np.asarray(mask) != 0) & (free_t >= t(request))
    return np.where(valid, score.astype(np.float32),
                    np.finfo(np.float32).min).astype(np.float32)


def pod_slots(free, mask, request: int) -> np.ndarray:
    free = np.asarray(free)
    valid = (np.asarray(mask) != 0) & (free >= request)
    return np.where(valid, free // request, 0).astype(np.int32)


class Reference:
    def __init__(self, config: dict, busy: np.ndarray,
                 pod_sizes: List[int]) -> None:
        self.cfg = config
        self.G = g = config["gpus_per_node"]
        self.w = config["score_weights"]
        self.colocate = float(config["colocate_bonus"])
        self.island = max(1, int(config["nvlink_island"]))
        self.busy = busy.copy()
        self.leaf = leaf_ids(config)
        self.n_leaves = int(self.leaf[-1]) + 1
        self.spine = spine_of_leaf(config)
        self.start = np.searchsorted(self.leaf, np.arange(self.n_leaves + 1))
        self.free = (g - self.busy.sum(axis=1)).astype(np.int64)
        cap = np.bincount(self.leaf, minlength=self.n_leaves) * g
        self.cap = np.maximum(cap.astype(np.float32), np.float32(1.0))
        self.g_free = np.bincount(self.leaf, weights=self.free,
                                  minlength=self.n_leaves).astype(np.int64)
        self.g_used = cap.astype(np.int64) - self.g_free
        self.g_slots = {r: np.bincount(self.leaf, weights=self.free // r,
                                       minlength=self.n_leaves
                                       ).astype(np.int64)
                        for r in pod_sizes}
        self.quota = dict(config["tenants"])
        self.tenant_used: Dict[str, int] = {}
        self.placed: Dict[int, Tuple[str, tuple]] = {}
        self.pending: Dict[int, tuple] = {}       # uid -> (key, spec, tenant)

    # -- state ---------------------------------------------------------
    def _set_free(self, node: int, free: int) -> None:
        old = int(self.free[node])
        if old == free:
            return
        lf = int(self.leaf[node])
        self.free[node] = free
        self.g_free[lf] += free - old
        self.g_used[lf] -= free - old
        for r, s in self.g_slots.items():
            s[lf] += free // r - old // r

    def _commit(self, uid: int, tenant: str, n_gpus: int,
                pods: tuple) -> None:
        for node, gpus in pods:
            self.busy[node, list(gpus)] = True
            self._set_free(node, self.G - int(self.busy[node].sum()))
        self.placed[uid] = (tenant, pods, n_gpus)
        self.tenant_used[tenant] = self.tenant_used.get(tenant, 0) + n_gpus

    def release(self, uid: int) -> None:
        rec = self.placed.pop(uid, None)
        if rec is None:
            return
        tenant, pods, n_gpus = rec
        for node, gpus in pods:
            self.busy[node, list(gpus)] = False
            self._set_free(node, self.G - int(self.busy[node].sum()))
        self.tenant_used[tenant] -= n_gpus

    # -- RSCH ----------------------------------------------------------
    def groups_for(self, n_pods: int, request: int) -> Optional[List[int]]:
        slots = self.g_slots[request]
        cand = np.nonzero(slots > 0)[0]
        if len(cand) == 0 or int(slots.sum()) < n_pods:
            return None
        whole = cand[slots[cand] >= n_pods]
        if len(whole):
            best = whole[np.lexsort((whole, self.g_free[whole],
                                     -self.g_used[whole]))[0]]
            return [int(best)]
        seed = int(cand[np.lexsort((cand, -slots[cand]))[0]])
        rest = cand[cand != seed]
        rest = rest[np.lexsort((rest, -slots[rest],
                                self.spine[rest] != self.spine[seed]))]
        covered = int(slots[seed]) + np.cumsum(slots[rest])
        k = int(np.searchsorted(covered, n_pods)) + 1
        return [seed] + [int(g) for g in rest[:k]]

    def level2_inputs(self, groups: List[int]):
        """Full-width inputs of the score pass for a group choice:
        mask, group load and anchor rank, as the configuration states
        them."""
        anchor_g, load_g = self._group_terms(groups)
        anchor = anchor_g[self.leaf]
        mask = (anchor > 0).astype(np.int32)
        return mask, load_g[self.leaf], anchor

    def _group_terms(self, groups: List[int]):
        """Per group: the anchor rank term ``1/(1 + rank)`` of the chosen
        groups (0 elsewhere) and the load ``used / capacity``."""
        anchor_g = np.zeros(self.n_leaves, np.float32)
        for rank, g in enumerate(groups):
            anchor_g[g] = np.float32(1.0 / (1.0 + rank))
        return anchor_g, self.g_used.astype(np.float32) / self.cap

    def _members(self, groups: List[int]) -> np.ndarray:
        return np.concatenate([np.arange(self.start[g], self.start[g + 1])
                               for g in sorted(groups)])

    def select_nodes(self, groups: List[int], n_pods: int,
                     request: int) -> Optional[List[int]]:
        nodes = self._members(groups)
        anchor_g, load_g = self._group_terms(groups)
        lf = self.leaf[nodes]
        free = self.free[nodes]
        s = fused_scores(free, self.G - free, np.ones(len(nodes)),
                         load_g[lf], anchor_g[lf], request, self.G, self.w)
        slots = pod_slots(free, np.ones(len(nodes)), request)
        if int(slots.sum()) < n_pods:
            return None
        fit = float(self.w["fit"])
        base = s.astype(np.float64) - fit * (free == request)
        taken = np.zeros(len(nodes), np.int64)

        def worth(i: int) -> float:
            k = taken[i]
            if k >= slots[i]:
                return -np.inf
            return (base[i] + self.colocate * k
                    + fit * (free[i] - k * request == request))

        val = np.where(slots > 0, s.astype(np.float64), -np.inf)
        out = []
        for _ in range(n_pods):
            i = int(np.argmax(val))
            out.append(int(nodes[i]))
            taken[i] += 1
            val[i] = worth(i)
        return out

    def pick_gpus(self, avail: List[bool], k: int) -> Optional[tuple]:
        islands: Dict[int, List[int]] = {}
        for gpu, a in enumerate(avail):
            if a:
                islands.setdefault(gpu // self.island, []).append(gpu)
        flat = [gpu for isl in sorted(islands) for gpu in islands[isl]]
        if len(flat) < k:
            return None
        for isl in sorted(islands):
            if len(islands[isl]) >= k:
                return tuple(islands[isl][:k])
        return tuple(flat[:k])

    def place(self, n_pods: int, request: int,
              probe=None) -> Optional[tuple]:
        """The placement RSCH should make, or None.  ``probe(groups)``
        is called once the groups are chosen, before anything changes."""
        groups = self.groups_for(n_pods, request)
        if groups is None:
            return None
        if probe is not None:
            probe(groups)
        nodes = self.select_nodes(groups, n_pods, request)
        if nodes is None:
            return None
        avail = {n: (~self.busy[n]).tolist() for n in dict.fromkeys(nodes)}
        pods = []
        for n in nodes:
            gpus = self.pick_gpus(avail[n], request)
            if gpus is None:
                return None
            for gpu in gpus:
                avail[n][gpu] = False
            pods.append((n, gpus))
        return tuple(pods)

    # -- QSCH ----------------------------------------------------------
    def submit(self, spec, submit_time: float, tenant: str,
               priority: int) -> None:
        key = (-priority, submit_time, spec.n_gpus, spec.uid)
        self.pending[spec.uid] = (key, spec, tenant)

    def _quota_ok(self, spec, tenant: str) -> bool:
        return (self.tenant_used.get(tenant, 0) + spec.n_gpus
                <= self.quota.get(tenant, 0))

    def try_job(self, spec, tenant: str, probe=None) -> Optional[tuple]:
        if not self._quota_ok(spec, tenant):
            return None
        if int(self.g_slots[spec.gpus_per_pod].sum()) < spec.n_pods:
            return None
        pods = self.place(spec.n_pods, spec.gpus_per_pod, probe)
        if pods is not None:
            self._commit(spec.uid, tenant, spec.n_gpus, pods)
            del self.pending[spec.uid]
        return pods

    def cycle(self, probes: Optional[dict] = None) -> List[tuple]:
        """One Backfill cycle: every pending job within its tenant's
        quota once, in queue order.  Returns (uid, pods or None) per
        decision.  ``probes`` maps a uid to a ``probe(groups)`` for its
        placement."""
        probes = probes or {}
        queue = sorted((e for e in self.pending.values()
                        if self._quota_ok(e[1], e[2])), key=lambda e: e[0])
        return [(spec.uid, self.try_job(spec, tenant, probes.get(spec.uid)))
                for _, spec, tenant in queue]
