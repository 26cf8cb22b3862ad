"""The per-layer metrics that read the program's own spans: a traced
run of the tiny cell reports all eight for its traffic, the four parts
of the score call add up to the outside-timed call, and a run without
those spans (untraced, or a program that does not enter them) leaves
them out."""

import types

import pytest

from tiny_cell import tiny_cell
from bench.run import run_once
from bench.spec import load_reader

BASES = ("qsch_self_ms_per_job", "rsch_self_ms_per_attempt",
         "rsch_group_choice_ms", "rsch_slot_walk_ms", "score_upload_ms",
         "score_launch_ms", "score_wait_ms", "score_fetch_ms")


@pytest.mark.parametrize("traffic,variant", [("train-steady", "lat"),
                                             ("train-backlog", "tput")])
def test_traced_run_reports_program_spans(traffic, variant):
    r = run_once(tiny_cell(traffic), 2 ** 31 + 4321, 0.6, True,
                 on_chip=False, backend="interpret", log=lambda *a: None)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    for base in BASES:
        assert f"{base}.{variant}" in m, base
        assert m[f"{base}.{variant}"] >= 0, base
    call = m[f"score_call_ms.{variant}"]
    parts = sum(m[f"score_{p}_ms.{variant}"]
                for p in ("upload", "launch", "wait", "fetch"))
    assert 0.9 * call <= parts <= call
    assert (m[f"rsch_group_choice_ms.{variant}"]
            + m[f"rsch_slot_walk_ms.{variant}"]
            <= m[f"rsch_self_ms_per_attempt.{variant}"])


@pytest.mark.parametrize("base", BASES)
def test_reader_finds_nothing_without_the_spans(base):
    """A traced window of a program that enters none of these phases."""
    win = types.SimpleNamespace(
        phase_s={"snapshot": 0.1, "filter": 0.2, "score": 0.3},
        window_cycles=[types.SimpleNamespace(attempts=[(1, None, 0)])],
        calls=4, rsch_calls=4)
    assert load_reader(base)(types.SimpleNamespace(win=win)) is None
