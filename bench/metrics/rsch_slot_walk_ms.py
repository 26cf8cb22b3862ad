"""RSCH: the top-k slot walk (slot-walk phase, select_gang_slots) per
RSCH.schedule call, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "slot-walk", run.win.rsch_calls)
