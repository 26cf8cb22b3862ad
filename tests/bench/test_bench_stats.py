"""Percentiles and rates are taken over the whole window."""

import math
import types

import pytest

from tiny_cell import ROOT  # noqa: F401  (puts the checkout on the path)
from bench import readers
from bench.driver import Cycle, Window
from bench.stats import percentile, rate, union_length
from bench.traffic import JobSpec


def test_percentile_nearest_rank():
    values = list(range(1, 101))
    assert percentile(values, 50) == 50
    assert percentile(values, 95) == 95
    assert percentile(values, 100) == 100
    assert percentile([7.0], 95) == 7.0
    assert percentile([3, 1, 2], 50) == 2


@pytest.mark.parametrize("q", [0, -1, 101])
def test_percentile_rejects_q_out_of_range(q):
    with pytest.raises(ValueError):
        percentile([1.0], q)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 50)


def test_a_failure_can_only_raise_the_tail():
    ok = [1.0] * 95
    assert percentile(ok + [math.inf] * 5, 95) == 1.0
    assert percentile(ok + [math.inf] * 6, 95) == math.inf


def test_rate_and_union():
    assert rate(30, 2.0) == 15.0
    with pytest.raises(ValueError):
        rate(1, 0.0)
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert union_length([]) == 0


def _window(kind):
    w = Window(kind=kind, t0=100.0, deadline=110.0, t_close=110.5,
               seconds=10.0, t_stop=112.0)
    for uid, due in enumerate((0.5, 1.0, 9.0)):
        w.specs[uid] = JobSpec(uid=uid, n_pods=uid + 1, gpus_per_pod=8,
                               due=due, duration=1.0)
        w.window_uids.append(uid)
    w.decided_at = {0: 100.6, 1: 101.5}     # job 2 never decided
    c = Cycle(now=0.0, t_end=110.5, in_window=True,
              attempts=[(0, ((3, (0,)),), 0),
                        (1, ((4, (0, 1)), (5, (0, 1))), 1),
                        (2, None, -1)])
    w.cycles = [c, Cycle(now=1.0, t_end=111.0,
                         in_window=False,
                         attempts=[(2, ((9, (0,)),), 2)])]
    return w


def test_latency_counts_every_job_due_in_the_window():
    run = types.SimpleNamespace(win=_window("open_loop"))
    lat = run.win.decision_latencies_s()
    assert lat == pytest.approx([0.1, 0.5, 3.0])   # undecided: waited
    assert readers.decision_ms(run, 50) == pytest.approx(500.0)
    assert readers.decision_ms(run, 95) == pytest.approx(3000.0)
    assert readers.pods_per_s(run) is None


def test_pods_per_s_is_over_the_window_cycles_and_their_time():
    run = types.SimpleNamespace(win=_window("backlog"))
    # 3 pods placed in window cycles, over 110.5 - 100 s; the pod placed
    # after the window does not count.
    assert readers.pods_per_s(run) == pytest.approx(3 / 10.5)
    assert readers.decision_ms(run, 50) is None
    assert readers.attempts_per_placement(run) == pytest.approx(1.0)
