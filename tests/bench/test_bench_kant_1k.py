"""The kant-1k cell: 128 nodes where gangs wait on fragmentation and
Backfill places the jobs behind them.  A short traced window at a CPU
rate stays correct while the head is blocked, evicts nothing, and
reports the two QSCH spans; the bfloat16 control is not correct there;
every size of the mix fits on the nodes the background leaves idle."""

import dataclasses

import numpy as np

from tiny_cell import ROOT  # noqa: F401  (puts the checkout on the path)
from bench import spec
from bench.cluster import background_busy
from bench.control import plant_control
from bench.run import run_once
from bench.traffic import pod_shape

CELL = "kant-1k.train-steady"
# A seed whose 64- and 128-GPU gangs arrive in the first second, so that
# later gangs wait for them within the window.
SEED = 2 ** 31 + 1


def _cpu_cell(rate: float) -> spec.Cell:
    """The cell at ``rate``, its durations scaled so the window's own
    jobs hold the GPUs they hold at the cell's rate (as bench.sweep)."""
    cell = spec.load_cell(CELL)
    t = cell.traffic
    traffic = dict(t, rate_per_s=rate, grace_s=2.0,
                   mean_duration_s=t["mean_duration_s"] * t["rate_per_s"]
                   / rate)
    return dataclasses.replace(cell, traffic=traffic)


def test_blocked_gangs_stay_correct_and_report_qsch_spans():
    windows = []
    r = run_once(_cpu_cell(100.0), SEED, 1.5, True, on_chip=False,
                 backend="interpret", log=lambda *a: None, windows=windows)
    assert r["correct"], r["checks"]
    win = windows[0]
    attempts = [a for c in win.window_cycles for a in c.attempts]
    assert any(pods is None for _, pods, _ in attempts)
    assert win.preemptions == 0
    m = r["metrics"]
    for name in ("head_attempt_ms_per_cycle.lat",
                 "backfill_pass_ms_per_cycle.lat"):
        assert m[name]["value"] >= 0, name


def test_bf16_control_is_not_correct():
    """The control, the reference's score pass in bfloat16 in the
    kernel's place, differs in the sampled score calls on this cell's
    table, where most nodes tie."""
    r = run_once(_cpu_cell(100.0), SEED, 1.0, False, on_chip=False,
                 backend="interpret", plant=plant_control,
                 log=lambda *a: None)
    assert not r["correct"]
    assert r["checks"]["score_calls"]["value"] > 0


def test_every_size_fits_on_the_idle_nodes():
    """The background leaves whole nodes idle enough for the largest
    gang of the mix, on every seed tried."""
    cell = spec.load_cell(CELL)
    g = cell.config["gpus_per_node"]
    shapes = [pod_shape(p["gpus"], g) for p in cell.traffic["population"]]
    for seed in [2 ** 31 + k for k in range(12)]:
        busy = background_busy(cell.config, seed)
        idle = int(np.count_nonzero(~busy.any(axis=1)))
        for n_pods, per_pod in shapes:
            assert idle * (g // per_pod) >= n_pods, (seed, n_pods, per_pod)
