"""QSCH, Backfill: the pass over the rest of the queue behind the head
(backfill-pass phase) per cycle of the window, ms (traced runs)."""

from bench import program_spans


def read(run):
    return program_spans.phase_ms_per(run, "backfill-pass",
                                      len(run.win.window_cycles))
