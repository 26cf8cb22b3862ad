"""Host-device score call: the launch of the jitted Pallas call and the
reshape/slice of its outputs (score-launch phase) per score call, ms
(traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "score-launch", run.win.calls)
