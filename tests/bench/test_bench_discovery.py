"""The harness finds a cell's files by name: a new traffic mix, a
per-cell overlay or a new metric reader is only a new file."""

import json
import shutil

import pytest

from tiny_cell import ROOT
from bench import spec


@pytest.fixture
def checkout(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_every_cell_of_the_benchmark_loads():
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]


def test_a_new_traffic_file_is_found_by_name(checkout):
    base = json.loads((checkout / "bench/traffic/train-steady.json")
                      .read_text())
    base.update(rate_per_s=3.0, mean_duration_s=1.0)
    (checkout / "bench/traffic/train-burst.json").write_text(
        json.dumps(base))
    (checkout / "bench/traffic/train-burst").mkdir()
    (checkout / "bench/traffic/train-burst/kant-10k.json").write_text(
        json.dumps({"rate_per_s": 5.0}))
    (checkout / "bench/metrics/burst_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    b = json.loads((checkout / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "kant-10k.train-burst",
                           "config": "kant-10k", "traffic": "train-burst",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "burst_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "driver", "moves": "decision_p50_ms",
                           "workloads": ["kant-10k.train-burst"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("kant-10k.train-burst", root=checkout)
    assert cell.traffic["rate_per_s"] == 5.0        # the cell's overlay
    assert cell.traffic["mean_duration_s"] == 1.0   # the mix's own
    assert [m.name for m in cell.per_layer] == ["burst_ms"]
    assert cell.per_layer[0].read(None) == 1.5
    # Metrics without a workloads list go to every cell.
    assert [m.name for m in cell.end_to_end] == ["setup_s"]
    with pytest.raises(KeyError):
        spec.load_cell("kant-10k.train-burst")       # not in the real one


def test_a_missing_reader_is_an_error(checkout):
    (checkout / "bench/metrics/setup_s.py").unlink()
    with pytest.raises(FileNotFoundError):
        spec.load_cell("kant-10k.train-steady", root=checkout)


def test_a_variant_falls_back_to_its_base_reader(checkout):
    metrics = checkout / "bench/metrics"
    (metrics / "burst_ms.py").write_text("def read(run):\n    return 1.0\n")
    assert spec.load_reader("burst_ms.lat", metrics)(None) == 1.0
    # A variant's own file comes first.
    (metrics / "burst_ms.lat.py").write_text(
        "def read(run):\n    return 2.0\n")
    assert spec.load_reader("burst_ms.lat", metrics)(None) == 2.0
    assert spec.load_reader("burst_ms.tput", metrics)(None) == 1.0
    with pytest.raises(FileNotFoundError):
        spec.load_reader("nothing_ms.lat", metrics)
