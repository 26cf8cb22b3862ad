"""Host-device score call: the copy of scores and slot counts to host
numpy arrays (score-fetch phase) per score call, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "score-fetch", run.win.calls)
