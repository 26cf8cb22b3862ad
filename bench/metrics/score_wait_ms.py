"""Host-device score call: waiting for the device to finish the call
(score-wait phase, block_until_ready, which the program makes only
under an observer) per score call, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "score-wait", run.win.calls)
