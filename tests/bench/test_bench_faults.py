"""Each fault planted under the timed path makes ``correct`` false; the
harness's look for a chip is skipped, the rest of a run is driven."""

import pytest

from tiny_cell import tiny_cell
from bench import faults
from bench.run import run_once


@pytest.mark.parametrize("fault, number", [
    (faults.state_unchanged, "gpu_state"),
    (faults.half_the_nodes, "score_calls"),
    (faults.altered_answer, "decisions"),
])
def test_fault_is_caught(fault, number):
    r = run_once(tiny_cell("train-backlog"), 4242, 0.6, False,
                 on_chip=False, backend="ref", plant=fault,
                 log=lambda *a: None)
    assert not r["correct"]
    assert r["checks"][number]["value"] > 0


def test_sound_run_is_correct():
    r = run_once(tiny_cell("train-backlog"), 4242, 0.6, False,
                 on_chip=False, backend="ref", log=lambda *a: None)
    assert r["correct"], r["checks"]
