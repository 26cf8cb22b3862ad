"""Host-device score call: host time per call of
repro.kernels.ops.node_scores_and_slots until scores and slot counts
are numpy arrays, ms."""

from bench import readers


def read(run):
    return readers.score_call_ms(run)
