"""RSCH: host time in RSCH.schedule less the score calls made in it
(filter, NodeNetGroup choice, score terms, slot and device selection),
per call, ms (traced runs)."""

from bench import readers


def read(run):
    return readers.rsch_host_ms_per_attempt(run)
