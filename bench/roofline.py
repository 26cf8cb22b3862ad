"""The work of the fused filter+score pass, counted from what it is
handed, not from how one implementation lays it out.

Per node the pass reads five fields of the node table at their
declared widths (``repro.core.columns``: free and used GPU counts as
int32; the pool mask as bool; the group load and anchor-rank terms as
float32) and writes the node's score (float32) and pod-slot count
(int32).  Padding rows, wider copies of a field or scoring fewer nodes
than were handed in do not add to the count, so the share computed
from it cannot pass 100% by being counted over more bytes than the
pass needs.  It does no matrix work: it is bound by memory.
"""

from __future__ import annotations

FIELD_BYTES_IN = {"free": 4, "used": 4, "mask": 1, "group_load": 4,
                  "topo_pref": 4}
FIELD_BYTES_OUT = {"score": 4, "slots": 4}
BYTES_PER_NODE = sum(FIELD_BYTES_IN.values()) + sum(FIELD_BYTES_OUT.values())


def score_pass_bytes(nodes: int) -> int:
    """HBM bytes the score pass needs for ``nodes`` nodes handed in."""
    return nodes * BYTES_PER_NODE


def roofline_share(nodes: int, kernel_seconds: float,
                   hbm_bytes_per_s: float) -> float:
    """Least time the pass could take at the chip's memory bandwidth,
    over the time its kernel took, in percent."""
    if kernel_seconds <= 0:
        raise ValueError("roofline share needs a kernel time above 0")
    return 100.0 * score_pass_bytes(nodes) / hbm_bytes_per_s / kernel_seconds
