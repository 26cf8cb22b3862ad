"""Driver: 95th percentile of how late the generator handed a job to
QSCH after its due time (or after the cycle running then), ms."""

from bench import readers


def read(run):
    return readers.pacer_lag_p95_ms(run)
