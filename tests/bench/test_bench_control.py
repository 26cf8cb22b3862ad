"""The control -- the reference's score pass in bfloat16 in the kernel's
place -- has to come out as not correct."""

from tiny_cell import tiny_cell
from bench.control import plant_control
from bench.run import run_once


def test_bf16_control_is_not_correct():
    r = run_once(tiny_cell("train-backlog"), 31337, 0.6, False,
                 on_chip=False, backend="ref", plant=plant_control,
                 log=lambda *a: None)
    assert not r["correct"]
    assert r["checks"]["score_calls"]["value"] > 0
