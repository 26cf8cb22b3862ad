"""The generator: the seed alone decides the jobs; every seed gets the
same work in another order."""

import collections
import json

import numpy as np
import pytest

from tiny_cell import ROOT
from bench import spec
from bench.cluster import background_busy
from bench.traffic import JobStream, pod_shape, pod_sizes

BIG_SEED = 2 ** 31 + 12345


def _jobs(traffic, seed, n):
    s = JobStream(traffic, 8, seed)
    return [s[i] for i in range(n)]


@pytest.mark.parametrize("name", ["train-steady", "train-backlog"])
def test_same_seed_same_jobs(name):
    t = spec.load_traffic(name, "kant-10k")
    a, b = _jobs(t, BIG_SEED, 1500), _jobs(t, BIG_SEED, 1500)
    assert a == b
    c = _jobs(t, BIG_SEED + 1, 1500)
    assert [j.n_gpus for j in a] != [j.n_gpus for j in c]


def test_every_seed_gets_the_same_block_of_work():
    """Each block holds the population's sizes; the seed picks their
    order, the gaps and the run times."""
    t = spec.load_traffic("train-steady", "kant-10k")
    want = {p["gpus"]: p["per_block"] for p in t["population"]}
    orders = []
    for seed in (1, 2, BIG_SEED):
        jobs = _jobs(t, seed, 2000)
        for b in (jobs[:1000], jobs[1000:]):
            assert collections.Counter(j.n_gpus for j in b) == want
        orders.append([j.n_gpus for j in jobs[:1000]])
    assert orders[0] != orders[1] != orders[2]


def test_arrivals_are_poisson():
    """Independent exponential gaps at the stated rate: the count in a
    fixed stretch varies as much as its mean (dispersion about 1), and
    is not smoothed by the generator."""
    t = spec.load_traffic("train-steady", "kant-10k")
    rate = t["rate_per_s"]
    jobs = _jobs(t, BIG_SEED, 20000)
    due = np.array([j.due for j in jobs])
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.03)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    width = 20 / rate                      # 20 arrivals on average
    counts = np.bincount((due // width).astype(int))[:-1]
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.15)


def test_run_times_are_exponential():
    t = spec.load_traffic("train-steady", "kant-10k")
    jobs = _jobs(t, 5, 20000)
    ones = np.array([j.duration for j in jobs if j.n_gpus == 1])
    scale = {p["gpus"]: p["duration_scale"] for p in t["population"]}[1]
    mean = t["mean_duration_s"] * scale
    assert ones.mean() == pytest.approx(mean, rel=0.03)
    assert ones.std() / ones.mean() == pytest.approx(1.0, abs=0.05)


def test_pod_shapes():
    assert pod_shape(4, 8) == (1, 4)
    assert pod_shape(2048, 8) == (256, 8)
    with pytest.raises(ValueError):
        pod_shape(12, 8)
    t = spec.load_traffic("train-steady", "kant-10k")
    assert pod_sizes(t, 8) == [1, 2, 4, 8]


def test_background_by_seed():
    cfg = json.loads((ROOT / "bench/configs/kant-10k.json").read_text())
    a, b = background_busy(cfg, BIG_SEED), background_busy(cfg, BIG_SEED)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, background_busy(cfg, 7))
    assert a.shape == (10_000, 8)
    assert 0.70 < a.mean() < 0.74                  # GAR about 0.72
    # Busy GPUs are the lowest slots of each node.
    assert np.array_equal(a, np.sort(a, axis=1)[:, ::-1])
