"""QSCH, Backfill: the blocked-or-placed head's attempt (head-attempt
phase: its try_place, with any preemption step) per cycle of the
window, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "head-attempt",
                                      len(run.win.window_cycles))
