"""Integration: Kant scheduling + the workloads it places (cosched)."""

import jax
import numpy as np
import pytest

from repro.core import (ClusterState, Job, JobKind, QSCH, QSCHConfig,
                        QueuePolicy, QuotaManager, RSCH, RSCHConfig,
                        SimConfig, Simulator, Strategy, training_trace)
from repro.core.topology import small_topology
from repro.launch.cosched import (effective_collective_bw,
                                  estimated_step_time, job_mesh_shape,
                                  placement_quality)
from repro.launch.cosched import ICI_BW
from repro.launch.mesh import make_cpu_mesh


def _run_sim(strategy, jobs, n_nodes=16):
    topo = small_topology(n_nodes=n_nodes, gpus_per_node=8,
                          nodes_per_leaf=4)
    state = ClusterState.create(topo)
    qm = QuotaManager({"t0": {0: 100000}})
    qsch = QSCH(qm, RSCH(topo, RSCHConfig(train_strategy=strategy)),
                QSCHConfig(policy=QueuePolicy.BACKFILL))
    sim = Simulator(state, qsch, SimConfig())
    return topo, sim.run([Job(**{**j.__dict__}) for j in _fresh(jobs)])


def _fresh(jobs):
    out = []
    for j in jobs:
        out.append(Job(uid=j.uid, tenant=j.tenant, gpu_type=j.gpu_type,
                       n_pods=j.n_pods, gpus_per_pod=j.gpus_per_pod,
                       kind=j.kind, gang=j.gang, priority=j.priority,
                       submit_time=j.submit_time, duration=j.duration))
    return out


def test_placement_quality_and_step_time():
    topo = small_topology(n_nodes=16, gpus_per_node=8, nodes_per_leaf=4)
    from repro.core import Placement, PodPlacement
    good = Placement(pods=[PodPlacement(node=n,
                                        gpu_indices=tuple(range(8)))
                           for n in (0, 1)])          # same leaf
    bad = Placement(pods=[PodPlacement(node=n,
                                       gpu_indices=tuple(range(8)))
                          for n in (0, 4)])           # two leaves
    qg = placement_quality(good, topo, 16)
    qb = placement_quality(bad, topo, 16)
    assert qg.group_dev == 1.0 and qb.group_dev == 2.0
    assert effective_collective_bw(qg) == ICI_BW
    assert effective_collective_bw(qb) < ICI_BW
    terms = {"compute": 0.1, "memory": 0.2, "collective": 0.3}
    assert estimated_step_time(terms, qb) > \
        estimated_step_time(terms, qg)


def test_ebinpack_placements_beat_spread_in_perf_model():
    """The beyond-paper loop: E-Binpack's placements give lower estimated
    step time than Spread for multi-node training jobs."""
    jobs = [j for j in training_trace(40, seed=7,
                                      arrival_rate_per_hour=240,
                                      mean_duration_s=1200.0)
            if j.n_gpus <= 64]
    est = {}
    for strat in (Strategy.E_BINPACK, Strategy.SPREAD):
        topo, result = _run_sim(strat, jobs)
        times = []
        for j in result.jobs:
            if j.placement is None or j.n_gpus < 16:
                continue
            q = placement_quality(j.placement, topo, j.n_gpus)
            terms = {"compute": 1.0, "memory": 1.0, "collective": 2.0}
            times.append(estimated_step_time(terms, q))
        est[strat] = float(np.mean(times)) if times else 0.0
    assert est[Strategy.E_BINPACK] <= est[Strategy.SPREAD] + 1e-9


def test_job_mesh_shape_factorization():
    assert job_mesh_shape(64) == (8, 8)
    assert job_mesh_shape(8) == (1, 8)
    assert job_mesh_shape(6) == (3, 2)
    assert job_mesh_shape(1) == (1, 1)


def test_scheduled_job_trains_on_cpu_mesh():
    """Close the loop end-to-end: schedule a job with Kant, build a mesh
    from its placement size, run one real train step under it."""
    from repro.core.snapshot import FullSnapshotter
    from repro.configs import get_arch, make_inputs
    from repro.models import Model
    from repro.sharding.auto import ShardingRules, param_shardings
    from repro.train import AdamWConfig, adamw_init, make_train_step

    topo = small_topology(n_nodes=4, gpus_per_node=1)
    state = ClusterState.create(topo)
    rsch = RSCH(topo)
    job = Job(uid=1, tenant="t0", gpu_type=0, n_pods=1, gpus_per_pod=1,
              kind=JobKind.TRAIN)
    res = rsch.schedule(job, FullSnapshotter().take(state))
    assert res.placement is not None
    data, model_par = job_mesh_shape(res.placement.n_gpus)
    # 1 GPU -> (1,1) mesh over the single real CPU device
    mesh = make_cpu_mesh(data, model_par)
    cfg = get_arch("glm4-9b", smoke=True)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    shardings = param_shardings(params, ShardingRules(mesh))
    params = jax.device_put(params, shardings)
    step = jax.jit(make_train_step(cfg, AdamWConfig(), remat=False))
    batch = make_inputs(cfg, batch=2, seq=16, kind="train")
    _, _, metrics = step(params, adamw_init(params), batch)
    assert np.isfinite(float(metrics["loss"]))


def test_chip_peaks_table_is_keyed_by_device_kind():
    from repro.launch.mesh import V5E, chip_peaks
    assert chip_peaks(V5E).flops_bf16 == 197e12
    assert chip_peaks(V5E).ici_bw == ICI_BW
    with pytest.raises(KeyError):
        chip_peaks("TPU v1 unknown")


def test_compile_cache_dir_env_wins_else_repo_path(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
