"""QSCH: time in the cycles less RSCH and the snapshot phase (queue
sort, admission, reserve/permit, bind, preemption), per job decided in
the window, ms (traced runs)."""

from bench import readers


def read(run):
    return readers.qsch_ms_per_job(run)
