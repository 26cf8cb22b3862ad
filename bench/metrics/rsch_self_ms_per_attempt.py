"""RSCH, timed inside the program: the rsch-schedule phase less the
four score-* phases of the score call, per RSCH.schedule call, ms
(traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.rsch_self_ms_per_attempt(run)
