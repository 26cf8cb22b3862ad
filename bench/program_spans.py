"""Arithmetic of the metric readers that read the program's own spans.

The program enters these phases through ``obs_phase`` where the work
happens: ``qsch-cycle`` (QSCH), ``rsch-schedule``, ``group-choice`` and
``slot-walk`` (RSCH), and ``score-upload``, ``score-launch``,
``score-wait`` and ``score-fetch`` (the score call in
``repro.kernels.ops``).  The benchmark's observer sums each over the
window's cycles into ``Window.phase_s`` in traced runs.  A reader
returns None where the phase it reads is absent: an untraced run, or a
program that does not enter it.
"""

from __future__ import annotations

from typing import Optional

from .readers import _decided_in_window

SCORE_PARTS = ("score-upload", "score-launch", "score-wait",
               "score-fetch")


def phase_ms_per(run, name: str, count: int) -> Optional[float]:
    """Milliseconds of phase ``name`` per ``count``."""
    seconds = run.win.phase_s.get(name)
    if seconds is None or not count:
        return None
    return 1e3 * seconds / count


def qsch_self_ms_per_job(run) -> Optional[float]:
    """QSCH's own time: ``qsch-cycle`` less ``rsch-schedule`` and the
    snapshot phase, per job decided."""
    ph = run.win.phase_s
    if "qsch-cycle" not in ph or "rsch-schedule" not in ph:
        return None
    jobs = _decided_in_window(run.win)
    if not jobs:
        return None
    own = ph["qsch-cycle"] - ph["rsch-schedule"] - ph.get("snapshot", 0.0)
    return 1e3 * own / jobs


def rsch_self_ms_per_attempt(run) -> Optional[float]:
    """RSCH's own time: ``rsch-schedule`` less the four parts of the
    score call, per ``RSCH.schedule`` call."""
    win = run.win
    ph = win.phase_s
    if "rsch-schedule" not in ph or not win.rsch_calls:
        return None
    own = ph["rsch-schedule"] - sum(ph.get(p, 0.0) for p in SCORE_PARTS)
    return 1e3 * own / win.rsch_calls
