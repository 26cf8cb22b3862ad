"""Median decision latency over every job due in the window, ms:
from the job's due time to the end of the first cycle that decided it."""

from bench import readers


def read(run):
    return readers.decision_ms(run, 50)
