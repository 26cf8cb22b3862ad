"""QSCH: placement attempts (placed, requeued, found infeasible) per
job placed in the window."""

from bench import readers


def read(run):
    return readers.attempts_per_placement(run)
