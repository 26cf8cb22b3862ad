"""Host-device score call: the upload, padding and reshape of the five
node columns (score-upload phase) per score call, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "score-upload", run.win.calls)
