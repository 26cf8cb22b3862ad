"""Snapshot: time in the snapshot phase per cycle, ms (traced runs)."""

from bench import readers


def read(run):
    return readers.snapshot_ms_per_cycle(run)
