"""The score pass's work is counted over the nodes handed in, at the
node table's declared widths."""

import types

import pytest

from tiny_cell import ROOT  # noqa: F401
from bench import readers, roofline
from bench.peaks import PEAKS, peaks_for


def test_bytes_per_node_at_declared_widths():
    # in: free i32, used i32, mask bool, group load f32, anchor f32;
    # out: score f32, slot count i32.
    assert roofline.BYTES_PER_NODE == 4 + 4 + 1 + 4 + 4 + 4 + 4
    assert roofline.score_pass_bytes(1_000_000) == 25_000_000


def test_share_is_least_time_over_kernel_time():
    bw = peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert bw == 819e9
    least = 25_000_000 / bw
    assert roofline.roofline_share(1_000_000, 2 * least, bw) == \
        pytest.approx(50.0)
    with pytest.raises(ValueError):
        roofline.roofline_share(10, 0.0, bw)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    assert all("hbm_bytes_per_s" in p for p in PEAKS.values())


def test_reader_counts_nodes_handed_in_not_padding():
    trace = types.SimpleNamespace(kernel_s=lambda key: 1e-3)
    calls = types.SimpleNamespace(nodes=10_000 * 40)   # 40 calls of 10k
    run = types.SimpleNamespace(trace=trace, calls=calls,
                                peaks={"hbm_bytes_per_s": 819e9})
    want = 100 * 40 * 10_000 * 25 / 819e9 / 1e-3
    assert readers.score_kernel_roofline(run) == pytest.approx(want)


def test_reader_is_silent_without_kernel_events():
    trace = types.SimpleNamespace(kernel_s=lambda key: 0.0)
    run = types.SimpleNamespace(trace=trace,
                                calls=types.SimpleNamespace(nodes=5),
                                peaks={"hbm_bytes_per_s": 819e9})
    assert readers.score_kernel_roofline(run) is None
    run.trace = None
    assert readers.score_kernel_roofline(run) is None
