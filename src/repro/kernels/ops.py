"""Public entry point for the node-scoring kernel.

``node_scores_and_slots`` is the call RSCH makes once per placement
attempt: it takes the natural 1-D node-table layout and returns every
node's fused filter+score value and its pod-slot count
``floor(free / request)``, from the Pallas TPU kernel or the pure-jnp
oracle.  For the kernel the columns are staged on the host as two
tables in its (rows, 128) tiling, padded to whole blocks (or, below one
block, to whole (8, 128) tiles, ``padded_nodes``, ``stage_tables``),
handed to the device in one transfer, and scored by one compiled
program that also slices the padding back off
(``scores_slots_program``).  Padding rows carry ``mask = 0``, so they
score ``-inf`` and hold no slots.

Backend selection:

* ``backend="pallas"``       — compiled Pallas kernel (TPU target);
* ``backend="interpret"``    — Pallas in interpret mode (CPU validation);
* ``backend="ref"``          — jnp oracle.

With an ``obs`` observer attached the call times its four parts as
phases (``score-upload``, ``score-launch``, ``score-wait``,
``score-fetch``) and counts ``score-h2d-bytes`` and ``score-d2h-bytes``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.framework.api import obs_count, obs_phase
from ..core.scoring import ScoreWeights
from . import node_score as _ns
from .ref import node_scores_slots_ref

_BLOCK = _ns.LANE * _ns.BLOCK_ROWS
_TILE = _ns.LANE * _ns.SUBLANE
# Padding of (free, used, mask, group_load, topo_pref): mask 0 rows.
_FILLS = (0, 0, 0, 0.0, 0.0)


def padded_nodes(n: int) -> int:
    """Nodes of the staged tables: ``n`` padded to whole blocks of
    ``BLOCK_ROWS`` rows, or, below one block, to whole ``(8, 128)``
    tiles, which the kernel scores as one block of that height."""
    if n < _BLOCK:
        return max(_TILE, -(-n // _TILE) * _TILE)
    return -(-n // _BLOCK) * _BLOCK


def stage_tables(cols: Sequence, n: int) -> tuple:
    """The five node columns staged on the host as the kernel reads
    them: free, used and mask as the rows of one int32 table, group_load
    and topo_pref of one float32 table, each ``(k, rows, LANE)`` in the
    kernel's tiling and padded to ``padded_nodes(n)`` with ``_FILLS``.
    Each column is cast to the dtype the kernel casts it to.  Returns
    ``(ints, floats)``."""
    padded = padded_nodes(n)
    ints = np.empty((3, padded), np.int32)
    floats = np.empty((2, padded), np.float32)
    for row, col, fill in zip((*ints, *floats), cols, _FILLS):
        row[:n] = col
        row[n:] = fill
    return ints.reshape(3, -1, _ns.LANE), floats.reshape(2, -1, _ns.LANE)


@functools.partial(jax.jit, static_argnames=("n",) + _ns.STATIC_ARGNAMES)
def scores_slots_program(ints, floats, *, n: int, **kw) -> jnp.ndarray:
    """The score+slots kernel over the staged tables and the slice of
    its output's padding, as one compiled program: one (2, 1, n) int32
    array, row 0 the float32 scores' bits and row 1 the pod slots, so
    the host fetches one buffer.  The unit axis keeps each row in the
    kernel output's memory order on a TPU, so the slice is the one op
    after the kernel; a (2, n) array is tiled two rows together there,
    which takes a relayout before the slice."""
    packed = _ns.node_scores_slots_tables(ints, floats, **kw)
    return packed.reshape(2, 1, -1)[..., :n]


def _kernel_kw(weights, request, gpus_per_node, w_used, w_fit, w_group,
               w_topo) -> dict:
    if weights is not None:
        w_used, w_fit = weights.used, weights.fit
        w_group, w_topo = weights.group, weights.topo
    return dict(request=request, gpus_per_node=gpus_per_node,
                w_used=w_used, w_fit=w_fit, w_group=w_group, w_topo=w_topo)


def node_scores_and_slots(free, used, mask, group_load, topo_pref, *,
                          request: int, gpus_per_node: int,
                          weights: Optional[ScoreWeights] = None,
                          w_used: float = 0.0, w_fit: float = 0.0,
                          w_group: float = 0.0, w_topo: float = 0.0,
                          backend: str = "ref", obs=None):
    """Fused (scores, pod_slots) pass for batched gang placement.

    One sweep over the node table yields both the per-node score and the
    number of pod slots ``floor(free / request)`` each node contributes
    (0 where invalid), feeding the whole-gang top-k slot selection in
    :func:`repro.core.scoring.select_gang_slots`.

    The ``pallas`` and ``interpret`` backends return host numpy arrays:
    the call stages the padded columns on the host as two tables and
    hands both to the device in one transfer (``score-upload``), makes
    one call of one compiled program, the kernel and the slice of its
    padding, and queues the copy of its one output to the host behind
    it (``score-launch``); then it takes that copy (``score-fetch``).
    Each part is a phase of ``obs`` when one is attached.  The scores
    and slots returned are views of the copy's two rows.  With an
    observer it first waits for the device apart (``score-wait``);
    without one the fetch waits.  The ``ref`` backend returns device
    arrays, untimed.
    """
    kw = _kernel_kw(weights, request, gpus_per_node, w_used, w_fit,
                    w_group, w_topo)
    if backend == "ref":
        return node_scores_slots_ref(
            jnp.asarray(free), jnp.asarray(used), jnp.asarray(mask),
            jnp.asarray(group_load), jnp.asarray(topo_pref), **kw)
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"unknown backend {backend!r}")

    n = np.shape(free)[0]
    with obs_phase(obs, "score-upload"):
        tables = stage_tables((free, used, mask, group_load, topo_pref), n)
        staged = jax.device_put(tables)
    with obs_phase(obs, "score-launch"):
        packed = scores_slots_program(
            *staged, n=n, interpret=(backend == "interpret"), **kw)
        # The copy queues behind the kernel on the device, so the host
        # pays one wait for compute and copy together, not one per
        # buffer after the fact (~0.4 ms a round trip on a TPU v5e).
        packed.copy_to_host_async()
    if obs is not None:
        # Only under an observer: the block is one more host-device round
        # trip (0.4-0.6 ms per call on a TPU v5e, PERF.md) that the fetch
        # below otherwise folds into its own wait.
        with obs_phase(obs, "score-wait"):
            packed.block_until_ready()
    with obs_phase(obs, "score-fetch"):
        packed = np.asarray(packed).reshape(2, n)
        scores, slots = packed[0].view(np.float32), packed[1]
    if obs is not None:
        obs_count(obs, "score-h2d-bytes", sum(t.nbytes for t in tables))
        obs_count(obs, "score-d2h-bytes", packed.nbytes)
    return scores, slots


def wkv6(r, k, v, w, u, s0, *, backend: str = "ref", tb: int = 256):
    """RWKV-6 WKV recurrence — kernel entry point.

    backend: "pallas" (compiled, TPU) | "interpret" (Pallas on CPU) |
    "ref" (jnp oracle).  See kernels/wkv6.py for the VMEM-residency
    argument; rwkv6.time_mix can call this in place of its step scan.
    """
    from .ref import wkv6_ref
    if backend == "ref":
        return wkv6_ref(r, k, v, w, u, s0)
    from .wkv6 import wkv6_pallas
    return wkv6_pallas(r, k, v, w, u, s0, tb=tb,
                       interpret=(backend == "interpret"))
