"""Pallas TPU kernel: fused node filter+score pass for RSCH.

At high scheduling QPS on 10⁴–10⁵-node clusters, the per-cycle hot loop is
"score every candidate node" (paper §3.4 attacks exactly this cost via
search-space reduction and snapshot memory optimization).  On the TPU
adaptation we additionally *fuse* the whole filter→score pipeline into a
single VPU pass over the node table:

* the node table (free, used, mask, group_load, topo_pref) is laid out as
  flat f32/int32 vectors in HBM;
* each grid step streams one ``(8, 128)``-aligned block into VMEM via the
  BlockSpec index map, evaluates the fused predicate+polynomial, and
  writes the score block back;
* invalid nodes get ``-inf`` so downstream ``argmax`` needs no extra mask.

The node axis is padded on the host by ``ops.py`` (``stage_tables``):
to whole blocks of ``BLOCK_ROWS`` rows, or, for a table smaller than one
block, to whole ``(8, 128)`` tiles, scored as one block of its own
height.  It hands the columns over as two tables that the
kernels read in place (``node_scores_tables``,
``node_scores_slots_tables``) and runs the kernel in one compiled
program with the slice of its padding; padding rows have ``mask = 0``
so they score ``-inf`` and can never win the argmax.

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


def _score_kernel(free_ref, used_ref, mask_ref, gload_ref, topo_ref,
                  out_ref, *, request: float, inv_g: float, w_used: float,
                  w_fit: float, w_group: float, w_topo: float) -> None:
    """Kernel body: one (BLOCK_ROWS, LANE) tile of the node table."""
    free = free_ref[...].astype(jnp.float32)
    used = used_ref[...].astype(jnp.float32)
    mask = mask_ref[...]
    gload = gload_ref[...]
    topo = topo_ref[...]
    valid = (mask != 0) & (free >= request)
    exact = (free == request).astype(jnp.float32)
    score = (w_used * used * inv_g + w_fit * exact
             + w_group * gload + w_topo * topo)
    out_ref[...] = jnp.where(valid, score, NEG_INF)


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


def _score_slots_kernel(free_ref, used_ref, mask_ref, gload_ref, topo_ref,
                        score_ref, slots_ref, **kw) -> None:
    """Scores and pod slots of one block, as two outputs."""
    score_ref[...], slots_ref[...] = _score_slots(
        free_ref, used_ref, mask_ref, gload_ref, topo_ref, **kw)


def _packed_score_slots_kernel(free_ref, used_ref, mask_ref, gload_ref,
                               topo_ref, out_ref, **kw) -> None:
    """Scores and pod slots of one block as the two rows of one int32
    output: the scores' float32 bits, then the slots.  One output is
    one buffer for the host to fetch."""
    score, slots = _score_slots(free_ref, used_ref, mask_ref, gload_ref,
                                topo_ref, **kw)
    out_ref[0] = jax.lax.bitcast_convert_type(score, jnp.int32)
    out_ref[1] = slots


# The kernels' static arguments: each value compiles its own variant.
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


def _column(block: int, lead: tuple = ()) -> pl.BlockSpec:
    """One block of a ``(*lead, rows, LANE)`` array per grid step, whole
    along its ``lead`` dims: a ``(rows, LANE)`` column by default."""
    zeros = (0,) * len(lead)
    return pl.BlockSpec((*lead, block, LANE), lambda i: (*zeros, i, 0))


def _row(k: int, block: int) -> pl.BlockSpec:
    """One block of row ``k`` of a ``(k_rows, rows, LANE)`` table per
    grid step: the kernel reads the column straight out of the table."""
    return pl.BlockSpec((pl.squeezed, block, LANE), lambda i: (k, i, 0))


def _score_call(body, rows: int, in_specs, outs, name: str, *,
                request: int, gpus_per_node: int, w_used: float,
                w_fit: float, w_group: float, w_topo: float,
                interpret: bool = False):
    """``pallas_call`` of ``body`` over ``rows`` rows with the request and
    weights closed over; ``in_specs(block)`` gives the inputs' block
    specs, and ``outs`` each output's leading dims and dtype: ``()`` for
    a ``(rows, LANE)`` column, ``(k,)`` for a ``(k, rows, LANE)`` table.
    The custom call is named ``name``, the name a profiler trace shows
    for the kernel."""
    kw = dict(request=float(request), inv_g=1.0 / float(gpus_per_node),
              w_used=float(w_used), w_fit=float(w_fit),
              w_group=float(w_group), w_topo=float(w_topo))
    if body is not _score_kernel:
        kw["request_i"] = int(request)
    block = _block_rows(rows)
    return pl.pallas_call(
        functools.partial(body, **kw),
        grid=(rows // block,),
        in_specs=in_specs(block),
        out_specs=[_column(block, lead) for lead, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((*lead, rows, LANE), dt)
                   for lead, dt in outs],
        interpret=interpret,
        name=name,
    )


def _columns_specs(block: int) -> list:
    return [_column(block)] * 5


def _columns(free, used, mask, group_load, topo_pref) -> tuple:
    rows, lane = free.shape
    if lane != LANE:
        raise ValueError(f"lane dim must be {LANE}, got {lane}")
    return rows, (free.astype(jnp.int32), used.astype(jnp.int32),
                  mask.astype(jnp.int32), group_load.astype(jnp.float32),
                  topo_pref.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=STATIC_ARGNAMES)
def node_scores_pallas(free: jnp.ndarray, used: jnp.ndarray,
                       mask: jnp.ndarray, group_load: jnp.ndarray,
                       topo_pref: jnp.ndarray, **kw) -> jnp.ndarray:
    """Score a 2-D node table of five (rows, LANE) columns; ``rows`` a
    multiple of ``BLOCK_ROWS``, or of ``SUBLANE`` below it."""
    rows, cols = _columns(free, used, mask, group_load, topo_pref)
    return _score_call(_score_kernel, rows, _columns_specs,
                       [((), jnp.float32)], "node_scores_pallas",
                       **kw)(*cols)[0]


@functools.partial(jax.jit, static_argnames=STATIC_ARGNAMES)
def node_scores_slots_pallas(free: jnp.ndarray, used: jnp.ndarray,
                             mask: jnp.ndarray, group_load: jnp.ndarray,
                             topo_pref: jnp.ndarray, **kw):
    """Fused (scores, pod_slots) over five (rows, LANE) columns — the
    batched gang-placement front half.  Layout contract matches
    :func:`node_scores_pallas`."""
    rows, cols = _columns(free, used, mask, group_load, topo_pref)
    return _score_call(_score_slots_kernel, rows, _columns_specs,
                       [((), jnp.float32), ((), jnp.int32)],
                       "node_scores_slots_pallas", **kw)(*cols)


# The node table as ``repro.kernels.ops`` stages it: free, used and mask
# are the rows of one int32 ``(3, rows, LANE)`` table, group_load and
# topo_pref of one float32 ``(2, rows, LANE)`` table.  The kernels read
# their blocks straight out of the two, so nothing copies the table on
# the device before they run.
def _table_specs(block: int) -> list:
    return [_row(0, block), _row(1, block), _row(2, block), _row(0, block),
            _row(1, block)]


def node_scores_tables(ints: jnp.ndarray, floats: jnp.ndarray, **kw
                       ) -> jnp.ndarray:
    """:func:`node_scores_pallas` over the two staged tables."""
    return _score_call(_score_kernel, ints.shape[1], _table_specs,
                       [((), jnp.float32)], "node_scores_pallas", **kw)(
        ints, ints, ints, floats, floats)[0]


def node_scores_slots_tables(ints: jnp.ndarray, floats: jnp.ndarray,
                             **kw) -> jnp.ndarray:
    """:func:`node_scores_slots_pallas` over the two staged tables, as
    one ``(2, rows, LANE)`` int32 table: row 0 the scores' float32
    bits, row 1 the pod slots."""
    return _score_call(_packed_score_slots_kernel, ints.shape[1],
                       _table_specs, [((2,), jnp.int32)],
                       "node_scores_slots_pallas", **kw)(
        ints, ints, ints, floats, floats)[0]
