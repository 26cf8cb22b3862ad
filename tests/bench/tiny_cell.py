"""A cell small enough for the CPU: the shape and policy of kant-10k on
320 nodes, with the benchmark's traffic files cut to gangs of at most
64 GPUs so that every job can fit."""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

# (gpus, jobs per block of 100, duration scale): the section 5.1.1 shares
# of the benchmark's population up to 64 GPUs.
POPULATION = [(1, 40, 0.6), (2, 22, 0.6), (4, 18, 0.8), (8, 11, 1.0),
              (16, 4, 1.2), (32, 3, 1.5), (64, 2, 2.0)]


def tiny_cell(traffic: str, **overrides) -> spec.Cell:
    config = json.loads((HERE / "data" / "tiny.json").read_text())
    params = spec.load_traffic(traffic, "kant-10k")
    params.update(block_jobs=100, population=[
        {"gpus": g, "per_block": c, "duration_scale": d}
        for g, c, d in POPULATION])
    if params["arrival"] == "open_loop":
        params.update(rate_per_s=40.0, mean_duration_s=0.2, grace_s=2.0)
    else:
        params.update(jobs_per_cycle=8, mean_duration_s=2.0)
    params.update(check_every_calls=7, check_max_calls=6, **overrides)
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"kant-10k.{traffic}"
    return spec.Cell(
        name=name, config_name="tiny", traffic_name=traffic, chips=1,
        config=config, traffic=params,
        end_to_end=spec.cell_metrics(bench_json["end_to_end"], name),
        per_layer=spec.cell_metrics(bench_json["per_layer"], name))
