"""Pallas TPU kernel: fused node filter+score pass for RSCH.

At high scheduling QPS on 10⁴–10⁵-node clusters, the per-cycle hot loop is
"score every candidate node" (paper §3.4 attacks exactly this cost via
search-space reduction and snapshot memory optimization).  On the TPU
adaptation we additionally *fuse* the whole filter→score pipeline and
the gang's capacity expansion into a single VPU pass over the node
table:

* ``repro.kernels.ops`` stages the five node columns on the host as two
  tables (``stage_tables``): free, used and mask as the rows of one
  int32 ``(3, rows, LANE)`` table, group_load and topo_pref of one
  float32 ``(2, rows, LANE)`` table.  The kernel reads its blocks
  straight out of the two, so nothing copies the table on the device
  before it runs;
* each grid step streams one ``(8, 128)``-aligned block of every column
  into VMEM via the BlockSpec index maps, evaluates the fused
  predicate+polynomial, and writes one ``(2, block, LANE)`` int32 output
  block: row 0 the scores' float32 bits, row 1 the pod slots
  ``floor(free / request)``, so the host fetches one buffer;
* invalid nodes get ``-inf`` and 0 slots, so the downstream slot walk
  needs no extra mask.

The node axis is padded on the host to whole blocks of ``BLOCK_ROWS``
rows, or, for a table smaller than one block, to whole ``(8, 128)``
tiles, scored as one block of its own height.  Padding rows have
``mask = 0``, so they score ``-inf`` and hold no slots.  ``ops`` runs the
kernel in one compiled program with the slice of its padding
(``ops.scores_slots_program``).

Scalar parameters (request size, strategy weights) are closed over as
Python floats, so the kernel body stays branch-free and every distinct
(pod size, weight set) pair compiles its own variant: a 500-job
``training_trace`` on a 10,000-node cluster compiled the score+slots
kernel 4 times on a TPU v5e (pod sizes 1, 2, 4 and 8 GPUs under the one
E-Binpack weight set).  A weight write from the tuning layer compiles
another variant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float(jnp.finfo(jnp.float32).min)

# One VMEM tile: sublane × lane = (8, 128) for f32 — the native TPU vector
# register tiling; the node table is reshaped to (-1, LANE) rows.
SUBLANE = 8
LANE = 128
BLOCK_ROWS = 64  # rows of 128 lanes per grid step -> 8192 nodes per block


def _score_slots(free_ref, used_ref, mask_ref, gload_ref, topo_ref, *,
                 request: float, request_i: int, inv_g: float,
                 w_used: float, w_fit: float, w_group: float,
                 w_topo: float):
    """Fused score + capacity expansion for batched gang placement.

    Alongside every node's score the pass yields its pod-slot count
    ``floor(free / request)`` (0 where invalid), so one VPU pass over the
    node table feeds the whole-gang top-k slot selection — the per-pod
    rescoring loop disappears (§3.4).  Returns ``(scores, slots)`` of
    one block.
    """
    free_i = free_ref[...]
    free = free_i.astype(jnp.float32)
    used = used_ref[...].astype(jnp.float32)
    mask = mask_ref[...]
    gload = gload_ref[...]
    topo = topo_ref[...]
    valid = (mask != 0) & (free >= request)
    exact = (free == request).astype(jnp.float32)
    score = (w_used * used * inv_g + w_fit * exact
             + w_group * gload + w_topo * topo)
    return (jnp.where(valid, score, NEG_INF),
            jnp.where(valid, free_i // request_i, 0).astype(jnp.int32))


def _packed_score_slots_kernel(free_ref, used_ref, mask_ref, gload_ref,
                               topo_ref, out_ref, **kw) -> None:
    """Scores and pod slots of one block as the two rows of one int32
    output: the scores' float32 bits, then the slots.  One output is
    one buffer for the host to fetch."""
    score, slots = _score_slots(free_ref, used_ref, mask_ref, gload_ref,
                                topo_ref, **kw)
    out_ref[0] = jax.lax.bitcast_convert_type(score, jnp.int32)
    out_ref[1] = slots


# The kernel's static arguments: each value compiles its own variant.
STATIC_ARGNAMES = ("request", "gpus_per_node", "w_used", "w_fit",
                   "w_group", "w_topo", "interpret")


def _block_rows(rows: int) -> int:
    """Rows of one grid step: ``BLOCK_ROWS``, or the whole table where it
    is smaller than one block (a multiple of ``SUBLANE`` rows)."""
    if 0 < rows < BLOCK_ROWS and rows % SUBLANE == 0:
        return rows
    if rows % BLOCK_ROWS:
        raise ValueError(f"rows ({rows}) must be a multiple of "
                         f"{BLOCK_ROWS}, or of {SUBLANE} below it")
    return BLOCK_ROWS


def _row(k: int, block: int) -> pl.BlockSpec:
    """One block of row ``k`` of a ``(k_rows, rows, LANE)`` table per
    grid step: the kernel reads the column straight out of the table."""
    return pl.BlockSpec((pl.squeezed, block, LANE), lambda i: (k, i, 0))


def node_scores_slots_tables(ints: jnp.ndarray, floats: jnp.ndarray, *,
                             request: int, gpus_per_node: int,
                             w_used: float, w_fit: float, w_group: float,
                             w_topo: float, interpret: bool = False
                             ) -> jnp.ndarray:
    """Fused (scores, pod_slots) over the two staged tables, as one
    ``(2, rows, LANE)`` int32 table: row 0 the scores' float32 bits,
    row 1 the pod slots.  ``rows`` is a multiple of ``BLOCK_ROWS``, or of
    ``SUBLANE`` below it.  The custom call is named
    ``node_scores_slots_pallas``, the name a profiler trace shows for
    the kernel."""
    rows = ints.shape[1]
    block = _block_rows(rows)
    kernel = functools.partial(
        _packed_score_slots_kernel, request=float(request),
        request_i=int(request), inv_g=1.0 / float(gpus_per_node),
        w_used=float(w_used), w_fit=float(w_fit), w_group=float(w_group),
        w_topo=float(w_topo))
    return pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=[_row(0, block), _row(1, block), _row(2, block),
                  _row(0, block), _row(1, block)],
        out_specs=pl.BlockSpec((2, block, LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((2, rows, LANE), jnp.int32),
        interpret=interpret,
        name="node_scores_slots_pallas",
    )(ints, ints, ints, floats, floats)
