"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader returns a number, or None when its cell has nothing for it
to read; the harness leaves a None out of the result line.  Times are
over the window's cycles (``Window.window_cycles``); device numbers are
over the traced window.
"""

from __future__ import annotations

from typing import Optional

from . import roofline
from .stats import percentile, rate

SCORE_KERNEL = "node_scores_slots_pallas"


def decision_ms(run, q: float) -> Optional[float]:
    win = run.win
    if win.kind != "open_loop" or not win.window_uids:
        return None
    return 1e3 * percentile(win.decision_latencies_s(), q)


def pods_per_s(run) -> Optional[float]:
    win = run.win
    if win.kind != "backlog":
        return None
    pods = sum(len(a[1]) for a in win.placed_in_window())
    return rate(pods, win.t_close - win.t0)


def pacer_lag_p95_ms(run) -> Optional[float]:
    win = run.win
    if win.kind != "open_loop" or not win.pacer_lag:
        return None
    return 1e3 * percentile(win.pacer_lag, 95)


def _decided_in_window(win) -> int:
    return len({a[0] for c in win.window_cycles for a in c.attempts})


def qsch_ms_per_job(run) -> Optional[float]:
    """QSCH's own time: the cycles less RSCH and the snapshot phase,
    per job decided."""
    win = run.win
    jobs = _decided_in_window(win)
    if run.spans is None or not jobs:
        return None
    own = win.cycle_s - win.rsch_s - win.phase_s.get("snapshot", 0.0)
    return 1e3 * own / jobs


def attempts_per_placement(run) -> Optional[float]:
    win = run.win
    placed = len(win.placed_in_window())
    if not placed:
        return None
    return (placed + win.requeues + win.infeasible) / placed


def snapshot_ms_per_cycle(run) -> Optional[float]:
    win = run.win
    cycles = len(win.window_cycles)
    if run.spans is None or not cycles:
        return None
    return 1e3 * win.phase_s.get("snapshot", 0.0) / cycles


def rsch_host_ms_per_attempt(run) -> Optional[float]:
    """RSCH's host time: its ``schedule`` calls less the score calls
    made in them, per call."""
    win = run.win
    if run.spans is None or not win.rsch_calls:
        return None
    return 1e3 * (win.rsch_s - win.call_s) / win.rsch_calls


def score_call_ms(run) -> Optional[float]:
    win = run.win
    if not win.calls:
        return None
    return 1e3 * win.call_s / win.calls


def score_kernel_roofline(run) -> Optional[float]:
    """The kernel's share of its memory roofline over every call of the
    traced window; nothing when the kernel left no event."""
    if run.trace is None or run.peaks is None or not run.calls.nodes:
        return None
    kernel_s = run.trace.kernel_s(SCORE_KERNEL)
    if kernel_s <= 0:
        return None
    return roofline.roofline_share(run.calls.nodes, kernel_s,
                                   run.peaks["hbm_bytes_per_s"])


def device_idle_share(run) -> Optional[float]:
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
