"""The plain reference agrees with the program, at a tiny size with the
Pallas kernel in interpret mode, through a whole run of the harness:
every sampled score call bit for bit, every decision, the final GPUs."""

import pytest

from tiny_cell import tiny_cell
from bench.reference import fused_scores, pod_slots
from bench.run import run_once

W = {"used": 1.0, "fit": 0.5, "group": 0.75, "topo": 1.5}


@pytest.mark.parametrize("traffic", ["train-steady", "train-backlog"])
def test_reference_agrees_with_the_program(traffic):
    r = run_once(tiny_cell(traffic), 2 ** 31 + 77, 0.6, False,
                 on_chip=False, backend="interpret", log=lambda *a: None)
    assert r["correct"], r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    names = set(r["metrics"])
    assert "setup_s" in names
    assert names & {"decision_p50_ms", "pods_per_s"}


def test_fused_score_by_hand():
    import numpy as np
    free = np.array([8, 4, 2, 8, 0])
    s = fused_scores(free, 8 - free, [1, 1, 1, 0, 1],
                     np.float32([0.5] * 5), np.float32([1, 1, .5, 1, 1]),
                     4, 8, W)
    lo = np.finfo(np.float32).min
    assert s[0] == np.float32(0.375 + 1.5)               # not an exact fit
    assert s[1] == np.float32(0.5 + 0.5 + 0.375 + 1.5)   # exact fit
    assert s[2] == lo and s[3] == lo and s[4] == lo      # too small, masked
    assert pod_slots(free, [1, 1, 1, 0, 1], 4).tolist() == [2, 1, 0, 0, 0]
