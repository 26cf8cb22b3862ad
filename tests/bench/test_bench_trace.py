"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a trace recorded on a TPU v5e (three 10,000-node score calls)."""

import json

import pytest

from tiny_cell import HERE
from bench import trace as tr

KERNEL = "node_scores_slots_pallas"


@pytest.fixture(scope="module")
def events():
    data = json.loads((HERE / "data" / "trace_v5e_score_calls.json")
                      .read_text())
    return tr.from_rows(data["rows"])


def test_window_is_the_bench_window_span(events):
    lo, hi = tr.window(events)
    assert (lo, hi) == (52318498.0, 52318498.0 + 32888469.0)


def test_busy_is_the_union_of_device_ops_in_the_window(events):
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, lo, hi)
    assert list(ops) == ["/device:TPU:0"]
    # Brute force over 10 ns cells.
    cells = set()
    for e in ops["/device:TPU:0"]:
        cells.update(range(int(e.start_ns) // 10, int(e.end_ns) // 10))
    assert tr.busy_ns(ops) == pytest.approx(len(cells) * 10, rel=0.02)
    assert 0 < tr.busy_ns(ops) < hi - lo


def test_kernel_time_by_name(events):
    lo, hi = tr.window(events)
    s = tr.summarize(events)
    kern = [e for e in events if e.line == tr.OPS_LINE
            and e.name.startswith("%" + KERNEL)]
    assert len(kern) == 3
    assert s.kernel_s(KERNEL) == pytest.approx(
        sum(e.dur_ns for e in kern) * 1e-9)
    assert s.kernel_s("no_such_kernel") == 0.0
    names = [n for n, _ in s.device_ops]
    assert f"%{KERNEL}.1" in names and len(names) <= 10


def test_idle_gaps_add_up_and_go_to_the_open_host_span(events):
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, lo, hi)
    gaps = tr.idle_gaps(events, ops, lo, hi, k=100)
    total = sum(s for _, s in gaps)
    assert total == pytest.approx((hi - lo - tr.busy_ns(ops)) * 1e-9)
    # Nearly all of the window lies inside the three score calls.
    by_name = dict(gaps)
    assert by_name["score_call"] > 0.9 * total


def test_overlapping_ops_count_once():
    rows = [["/host:CPU", "python3", tr.WINDOW_SPAN, 0.0, 100.0, {}],
            ["/device:TPU:0", tr.OPS_LINE, "%a = f32[]", 10.0, 30.0, {}],
            ["/device:TPU:0", tr.OPS_LINE, "%b = f32[]", 20.0, 30.0, {}],
            ["/device:TPU:0", tr.OPS_LINE, "%c = f32[]", 90.0, 30.0, {}]]
    s = tr.summarize(tr.from_rows(rows))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(50e-9)     # 10-50 and 90-100
    assert s.idle_gaps == [["(no host span)", pytest.approx(50e-9)]]
