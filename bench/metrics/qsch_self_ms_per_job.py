"""QSCH, timed inside the program: the qsch-cycle phase less the
rsch-schedule and snapshot phases, per job decided in the window, ms
(traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.qsch_self_ms_per_job(run)
