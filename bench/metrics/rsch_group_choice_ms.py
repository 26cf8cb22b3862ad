"""RSCH: the level-1 NodeNetGroup choice (group-choice phase: pod and
group slots, group score terms, preselection, the group preference
table) per RSCH.schedule call, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "group-choice", run.win.rsch_calls)
