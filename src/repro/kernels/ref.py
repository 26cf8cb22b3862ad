"""Pure-jnp oracle for the fused node filter+score pass.

Semantics are identical to :func:`repro.core.scoring.node_scores_np` and
to the Pallas kernel in :mod:`repro.kernels.node_score`; all three are
asserted equal in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float(jnp.finfo(jnp.float32).min)


def node_scores_ref(free: jnp.ndarray, used: jnp.ndarray,
                    mask: jnp.ndarray, group_load: jnp.ndarray,
                    topo_pref: jnp.ndarray, *, request: int,
                    gpus_per_node: int, w_used: float, w_fit: float,
                    w_group: float, w_topo: float) -> jnp.ndarray:
    """Reference: score every node, -inf where invalid.

    Args:
      free:       (n,) int — healthy free devices per node.
      used:       (n,) int — healthy allocated devices per node.
      mask:       (n,) bool/int — node is in the candidate pool.
      group_load: (n,) f32 — load fraction of the node's NodeNetGroup,
                  pre-gathered to node axis.
      topo_pref:  (n,) f32 — anchor-group preference for this job.
    """
    free_f = free.astype(jnp.float32)
    used_f = used.astype(jnp.float32)
    valid = (mask != 0) & (free_f >= float(request))
    score = (w_used * used_f * (1.0 / float(gpus_per_node))
             + w_fit * (free_f == float(request)).astype(jnp.float32)
             + w_group * group_load.astype(jnp.float32)
             + w_topo * topo_pref.astype(jnp.float32))
    return jnp.where(valid, score, NEG_INF).astype(jnp.float32)


def node_scores_slots_ref(free: jnp.ndarray, used: jnp.ndarray,
                          mask: jnp.ndarray, group_load: jnp.ndarray,
                          topo_pref: jnp.ndarray, *, request: int,
                          gpus_per_node: int, w_used: float, w_fit: float,
                          w_group: float, w_topo: float):
    """Oracle for the fused (scores, pod_slots) batched-gang pass."""
    scores = node_scores_ref(free, used, mask, group_load, topo_pref,
                             request=request, gpus_per_node=gpus_per_node,
                             w_used=w_used, w_fit=w_fit, w_group=w_group,
                             w_topo=w_topo)
    free_i = free.astype(jnp.int32)
    valid = (mask != 0) & (free_i >= request)
    slots = jnp.where(valid, free_i // request, 0).astype(jnp.int32)
    return scores, slots


def wkv6_ref(r, k, v, w, u, s0):
    """Pure-jnp oracle for the RWKV-6 WKV recurrence.

    r, k, v, w: (B, T, H, n); u: (H, n); s0: (B, H, n, n).
    Returns (o (B, T, H, n) f32, sT (B, H, n, n) f32) — identical math to
    ``rwkv6.time_mix``'s step scan, kept standalone so the kernel test
    does not depend on the model layer.
    """
    r, k, v, w = (t.astype(jnp.float32) for t in (r, k, v, w))
    u = u.astype(jnp.float32)
    s0 = s0.astype(jnp.float32)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                     # (B, H, n)
        kv = k_t[..., :, None] * v_t[..., None, :]   # (B, H, n, n)
        o = jnp.einsum("bhn,bhnm->bhm", r_t, S + u[None, :, :, None] * kv)
        return w_t[..., :, None] * S + kv, o

    tr = lambda t: t.transpose(1, 0, 2, 3)           # (T, B, H, n)
    sT, oT = jax.lax.scan(step, s0, (tr(r), tr(k), tr(v), tr(w)))
    return oT.transpose(1, 0, 2, 3), sT
