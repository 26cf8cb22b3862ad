"""Observability subsystem (repro.obs): metric registry semantics,
Chrome-trace emission, decision audit attribution, telemetry facade
wiring, report tool, and the satellite publishers (serving pool,
combo caches)."""

import json
import math

import numpy as np
import pytest

from repro.core import (ClusterState, Job, JobKind, QueuePolicy,
                        QuotaManager, QSCH, QSCHConfig, RSCH, RSCHConfig,
                        SimConfig, Simulator, Strategy, small_topology,
                        training_trace)
from repro.core.workload import DEFAULT_QUERY_CLASSES, ServeRequest
from repro.launch.combo_cache import ComboCache, cache_stats
from repro.obs import (DEFAULT_BUCKETS, DecisionAudit, MetricRegistry,
                       ObserverPlugin, PID_JOBS, PID_SCHED,
                       PlacementDecision, Telemetry, Tracer,
                       build_report, render_markdown)
from repro.obs import report as report_mod
from repro.serve import LeastLoadedRouter, ReplicaPool, ReplicaSpec

from conftest import make_qsch


# ----------------------------------------------------------------------
# Metric registry
# ----------------------------------------------------------------------
def test_counter_gauge_labels_and_ring():
    reg = MetricRegistry(ring=4)
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.0, zone="a")
    assert c.value() == 1.0
    assert c.value(zone="a") == 2.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("depth")
    g.set(5.0)
    g.inc(1.5)
    assert g.value() == 6.5
    for i in range(10):
        g.set(float(i))
    assert len(g.series()) == 4          # ring-bounded
    assert g.series()[-1] == (0.0, 9.0)


def test_registry_clock_stamps_series():
    t = {"now": 0.0}
    reg = MetricRegistry(clock=lambda: t["now"])
    g = reg.gauge("x")
    g.set(1.0)
    t["now"] = 42.0
    g.set(2.0)
    assert g.series() == [(0.0, 1.0), (42.0, 2.0)]


def test_metric_type_conflict_raises():
    reg = MetricRegistry()
    reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")


def test_histogram_matches_numpy_reference():
    rng = np.random.default_rng(3)
    values = rng.uniform(0.0, 20_000.0, size=500)
    # Pin the boundary semantics: values exactly on a bound must land
    # in that bound's bucket (Prometheus `le`, i.e. value <= bound).
    values = np.concatenate([values, np.asarray(DEFAULT_BUCKETS)])
    reg = MetricRegistry()
    h = reg.histogram("lat", "latency")
    for v in values:
        h.observe(float(v))
    bounds = np.asarray(DEFAULT_BUCKETS)
    ref = [int((values <= b).sum()) for b in bounds] + [len(values)]
    assert h.cumulative() == ref


def test_prometheus_text_exposition():
    reg = MetricRegistry()
    reg.counter("jobs_total", "jobs").inc(3, tenant="t0")
    h = reg.histogram("wait", "queue wait", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.expose_text()
    assert "# HELP jobs_total jobs" in text
    assert "# TYPE jobs_total counter" in text
    assert 'jobs_total{tenant="t0"} 3' in text
    assert 'wait_bucket{le="1"} 1' in text
    assert 'wait_bucket{le="10"} 2' in text       # cumulative
    assert 'wait_bucket{le="+Inf"} 3' in text
    assert "wait_sum 105.5" in text
    assert "wait_count 3" in text


def test_pull_collectors_run_on_exposition():
    reg = MetricRegistry()
    calls = []

    def pull(r):
        calls.append(1)
        r.gauge("pulled").set(7.0)

    reg.add_collector(pull)
    assert "pulled 7" in reg.expose_text()
    doc = reg.to_json()
    assert doc["pulled"]["series"][0]["value"] == 7.0
    assert calls
    json.dumps(doc)                       # strictly serializable


# ----------------------------------------------------------------------
# Tracer (Chrome trace-event format)
# ----------------------------------------------------------------------
def _lane_balance(events):
    lanes = {}
    for e in events:
        if e["ph"] == "B":
            lanes[(e["pid"], e["tid"])] = lanes.get(
                (e["pid"], e["tid"]), 0) + 1
        elif e["ph"] == "E":
            lanes[(e["pid"], e["tid"])] = lanes.get(
                (e["pid"], e["tid"]), 0) - 1
    return lanes


def test_trace_event_schema_and_balance():
    tr = Tracer()
    tr.metadata(PID_SCHED, "scheduler (wall clock)")
    tr.begin("cycle", 0.0, PID_SCHED, 0, args={"t_sim": 0.0})
    tr.begin("filter", 1.0, PID_SCHED, 0)
    tr.end("filter", 6.0, PID_SCHED, 0)
    tr.instant("NODE_FAIL", 3.0, PID_SCHED, 0, args={"node": 4})
    tr.end("cycle", 10.0, PID_SCHED, 0)
    doc = tr.to_json()
    events = doc["traceEvents"]
    for e in events:
        assert {"ph", "name", "ts", "pid", "tid"} <= set(e)
    instants = [e for e in events if e["ph"] == "i"]
    assert instants and all(e["s"] == "t" for e in instants)
    # args are included only when present and truthy
    b_filter = next(e for e in events
                    if e["name"] == "filter" and e["ph"] == "B")
    assert "args" not in b_filter
    assert all(v == 0 for v in _lane_balance(events).values())
    json.dumps(doc)


def test_trace_close_all_tags_injected_ends():
    tr = Tracer()
    tr.begin("job-1", 0.0, PID_JOBS, 1)
    tr.begin("job-2", 5.0, PID_JOBS, 2)
    assert len(tr.open_spans()) == 2
    assert tr.close_all(50.0) == 2
    assert tr.open_spans() == {}
    ends = [e for e in tr.to_json()["traceEvents"] if e["ph"] == "E"]
    assert len(ends) == 2
    assert all(e["ts"] == 50.0 for e in ends)
    assert all(e["args"]["closed_at_finalize"] for e in ends)


def test_trace_event_cap_counts_drops():
    tr = Tracer(max_events=3)
    tr.instant("a", 0.0, PID_SCHED, 0)
    tr.instant("b", 1.0, PID_SCHED, 0)
    tr.begin("s", 2.0, PID_SCHED, 0)       # takes the last slot
    tr.end("s", 3.0, PID_SCHED, 0)
    assert tr.dropped == 1
    assert len(tr.to_json()["traceEvents"]) == 3


# ----------------------------------------------------------------------
# Decision audit through a real QSCH cycle
# ----------------------------------------------------------------------
def _gang(uid=1, pods=2, gpg=8, **kw):
    return Job(uid=uid, tenant="t0", gpu_type=0, n_pods=pods,
               gpus_per_pod=gpg, kind=JobKind.TRAIN, **kw)


def test_audit_breakdown_sums_to_fused_score(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.STRICT_FIFO)
    tel = Telemetry()
    tel.attach_qsch(qsch)
    qsch.submit(_gang())
    result = qsch.cycle(state, 0.0)
    assert len(result.scheduled) == 1
    (dec,) = tel.audit.bound()
    assert dec.outcome == "bound" and dec.reason == "ok"
    placement = result.scheduled[0].placement
    assert dec.nodes == sorted({p.node for p in placement.pods})
    pa = dec.passes[-1]
    assert pa.pool_size > 0
    for st in pa.filters:
        assert 0 <= st.nodes_after <= st.nodes_before
        assert st.eliminated == st.nodes_before - st.nodes_after
    assert pa.breakdown, "winning pass must carry a score breakdown"
    assert {b.node for b in pa.breakdown} == set(dec.nodes)
    for b in pa.breakdown:
        assert b.terms, "per-ScorePlugin terms present"
        assert math.isclose(sum(b.terms.values()), b.total,
                            rel_tol=1e-6, abs_tol=1e-9), \
            f"terms {b.terms} do not sum to fused total {b.total}"
    json.dumps(dec.as_dict())             # export path serializable


def test_audit_records_rejection_reason(topo, state):
    qsch = make_qsch(topo, state, policy=QueuePolicy.STRICT_FIFO)
    tel = Telemetry()
    tel.attach_qsch(qsch)
    # 64 pods x 8 GPUs on a 128-GPU cluster can never fit.
    qsch.submit(_gang(uid=9, pods=64))
    result = qsch.cycle(state, 0.0)
    assert not result.scheduled
    rej = tel.audit.rejected()
    assert rej and rej[0].uid == 9
    reason = rej[0].reason
    assert reason
    assert tel.audit.rejections_by_reason()[reason] >= 1


def test_preemption_record_names_plugin_and_beneficiary():
    class Ctx:
        now = 120.0

    tel = Telemetry()
    tel.emit_preempt(_gang(uid=7), Ctx(), ("TenantClawback", 11))
    (rec,) = tel.audit.preemptions
    assert rec.victim_uid == 7
    assert rec.beneficiary_uid == 11
    assert rec.plugin == "TenantClawback"
    assert rec.t == 120.0
    assert tel.registry.counter("kant_preemptions_total").value(
        plugin="TenantClawback") == 1.0


def test_audit_ring_cap_reports_drops():
    audit = DecisionAudit(max_records=2)
    for uid in range(5):
        audit.on_bind(None, PlacementDecision(
            uid=uid, tenant="t0", kind="TRAIN", outcome="bound",
            reason="ok", t=float(uid)), None)
    assert len(audit.decisions) == 2
    assert audit.dropped == 3
    assert audit.summary()["decisions"] == 5


def test_custom_observer_plugin_receives_taps(topo, state):
    class Recorder(ObserverPlugin):
        name = "RecorderTestOnly"

        def __init__(self):
            self.cycles = 0
            self.binds = []

        def on_cycle(self, span, ctx):
            self.cycles += 1

        def on_bind(self, job, decision, ctx):
            self.binds.append((job.uid, decision))

    rec = Recorder()
    qsch = make_qsch(topo, state)
    tel = Telemetry(observers=[rec])
    tel.attach_qsch(qsch)
    qsch.submit(_gang(uid=3))
    qsch.cycle(state, 0.0)
    assert rec.cycles == 1
    assert rec.binds and rec.binds[0][0] == 3
    # The built-in audit's decision object is shared with customs.
    assert rec.binds[0][1] is tel.audit.bound()[0]


# ----------------------------------------------------------------------
# Telemetry facade on a full simulator run
# ----------------------------------------------------------------------
def _trace_jobs(n=40, seed=11):
    jobs = training_trace(n, seed=seed, arrival_rate_per_hour=400,
                          mean_duration_s=1800.0)
    return [j for j in jobs if j.n_gpus <= 64]


def _run_sim(jobs, telemetry=None):
    topo = small_topology(n_nodes=32, gpus_per_node=8, nodes_per_leaf=4)
    state = ClusterState.create(topo)
    qm = QuotaManager({"t0": {0: 10**6}})
    rsch = RSCH(topo, RSCHConfig(train_strategy=Strategy.E_BINPACK))
    qsch = QSCH(qm, rsch, QSCHConfig(policy=QueuePolicy.BACKFILL))
    sim = Simulator(state, qsch,
                    SimConfig(tick_interval=30.0, sample_interval=300.0,
                              binding_latency=45.0))
    if telemetry is not None:
        telemetry.attach(sim)
    return sim, sim.run(jobs)


def _fingerprint(result):
    return [(j.uid, j.start_time, j.end_time,
             tuple((p.node, p.gpu_indices)
                   for p in (j.placement.pods if j.placement else ())))
            for j in result.jobs]


def test_detached_telemetry_is_byte_identical():
    base_sim, base = _run_sim(_trace_jobs())
    tel = Telemetry()
    inst_sim, inst = _run_sim(_trace_jobs(), telemetry=tel)
    assert _fingerprint(base) == _fingerprint(inst)
    assert base.metrics.report() == inst.metrics.report()
    assert tel.registry.counter("kant_cycles_total").value() > 0
    tel.detach(inst_sim)
    assert inst_sim.qsch.obs is None and inst_sim.qsch.rsch.obs is None


def test_job_spans_cover_run_and_lanes_balance():
    tel = Telemetry()
    _, result = _run_sim(_trace_jobs(), telemetry=tel)
    events = tel.tracer.to_json()["traceEvents"]
    begins = {e["name"] for e in events
              if e["ph"] == "B" and e["pid"] == PID_JOBS}
    assert begins == {f"job-{j.uid}" for j in result.jobs}
    assert all(v == 0 for v in _lane_balance(events).values())
    # Job lifecycle records accumulated waits consistent with the sim.
    recs = {r["uid"]: r for r in tel.job_records()}
    for j in result.jobs:
        if j.start_time is not None:
            assert recs[j.uid]["first_start"] == j.start_time
            assert recs[j.uid]["wait_s"] == j.start_time - j.submit_time


def _sched_spans(events):
    """(name, begin, end, depth, args) per span of the scheduler lane,
    parsed with a stack: a span that closes out of order fails."""
    stack, spans = [], []
    for e in events:
        if e["pid"] != PID_SCHED or e["ph"] not in "BE":
            continue
        if e["ph"] == "B":
            stack.append(e)
            continue
        b = stack.pop()
        assert b["name"] == e["name"], "spans overlap partially"
        args = dict(b.get("args") or {}, **(e.get("args") or {}))
        spans.append((b["name"], b["ts"], e["ts"], len(stack), args))
    assert not stack
    return spans


def test_trace_phases_nest_in_their_cycle_at_real_offsets():
    """Every phase span lies inside its ``qsch-cycle`` span, spans nest
    without partial overlap, and each sits at its own start and end:
    RSCH's phases nest inside ``rsch-schedule``, which no sequential
    re-layout of durations could give."""
    tel = Telemetry()
    _, result = _run_sim(_trace_jobs(), telemetry=tel)
    events = tel.tracer.to_json()["traceEvents"]
    sched = [e for e in events if e["pid"] == PID_SCHED and e["ph"] in "BE"]
    assert [e["ts"] for e in sched] == sorted(e["ts"] for e in sched)
    spans = _sched_spans(events)
    cycles = [s for s in spans if s[0] == "qsch-cycle"]
    assert cycles and all(s[3] == 0 for s in cycles)
    assert all(set(s[4]) == {"t_sim", "scheduled", "preempted",
                             "requeues"} for s in cycles)
    for name, t0, t1, depth, _ in spans:
        if name != "qsch-cycle":
            assert depth >= 1
            assert any(c[1] <= t0 <= t1 <= c[2] for c in cycles), name
    uids = {j.uid for j in result.jobs}
    schedules = [s for s in spans if s[0] == "rsch-schedule"]
    assert schedules and all(s[4]["uid"] in uids for s in schedules)
    for name in ("filter", "group-choice", "score", "slot-walk"):
        inner = [s for s in spans if s[0] == name]
        assert inner, name
        for _, t0, t1, _, _ in inner:
            assert any(r[1] <= t0 <= t1 <= r[2] for r in schedules), name
    # Telemetry's cycle is the qsch-cycle phase, not a second timer.
    assert math.isclose(tel.phase_totals["qsch-cycle"],
                        sum(c[2] - c[1] for c in cycles) / 1e6,
                        rel_tol=1e-6)


def test_profiler_trace_holds_the_program_phases(topo, state, tmp_path):
    """Under ``jax.profiler.trace`` the attached Telemetry's phases are
    host events of the ``.xplane.pb``, on the profiler's clock; the
    byte counters reach the registry and the Chrome trace."""
    import glob
    import jax
    qsch = make_qsch(topo, state, policy=QueuePolicy.STRICT_FIFO,
                     rsch_config=RSCHConfig(score_backend="interpret"))
    tel = Telemetry()
    tel.attach_qsch(qsch)
    qsch.submit(_gang(uid=5, pods=3))
    with jax.profiler.trace(str(tmp_path)):
        result = qsch.cycle(state, 0.0)
    assert len(result.scheduled) == 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"qsch-cycle", "snapshot", "rsch-schedule", "group-choice",
            "slot-walk", "score-upload", "score-launch", "score-wait",
            "score-fetch", "bind"} <= host
    h2d = tel.registry.counter("kant_score_h2d_bytes_total").value()
    d2h = tel.registry.counter("kant_score_d2h_bytes_total").value()
    assert h2d > 0 and d2h == 2 * 4 * topo.n_nodes
    counters = [e for e in tel.tracer.to_json()["traceEvents"]
                if e["ph"] == "C"]
    assert {e["name"] for e in counters} == {"score-h2d-bytes",
                                             "score-d2h-bytes"}
    assert counters[-1]["args"] == {"score-d2h-bytes": d2h}


def test_pillar_toggles_disable_cleanly():
    tel = Telemetry(registry=False, tracing=False, audit=False)
    assert tel.registry is None and tel.tracer is None
    assert tel.audit is None and not tel.audit_on
    with pytest.raises(ValueError):
        tel.save_trace("unused.json")
    bundle = tel.bundle()
    assert "metrics" not in bundle and "trace" not in bundle
    assert "audit" not in bundle
    assert bundle["meta"]["pillars"] == {"registry": False,
                                         "tracing": False,
                                         "audit": False}


# ----------------------------------------------------------------------
# Bundle + report tool
# ----------------------------------------------------------------------
def test_bundle_report_and_cli_roundtrip(tmp_path):
    tel = Telemetry()
    _run_sim(_trace_jobs(), telemetry=tel)
    bundle = tel.bundle()
    assert bundle["meta"]["format"] == "repro.obs/1"
    assert bundle["jobs"] and bundle["metrics"] and bundle["audit"]

    path = tmp_path / "bundle.json"
    tel.save(str(path))
    loaded = json.loads(path.read_text())
    report = build_report(loaded)
    assert report["summary"]["jobs_seen"] == len(bundle["jobs"])
    assert report["summary"]["jobs_completed"] > 0
    assert report["audit"]["bound"] == bundle["audit"]["summary"]["bound"]
    md = render_markdown(report)
    assert md.startswith("# Run telemetry report")
    assert "## Summary" in md and "## Metrics" in md

    out_md = tmp_path / "report.md"
    assert report_mod.main([str(path), "--format", "md",
                            "-o", str(out_md)]) == 0
    assert "# Run telemetry report" in out_md.read_text()
    out_js = tmp_path / "report.json"
    assert report_mod.main([str(path), "--format", "json",
                            "-o", str(out_js)]) == 0
    assert json.loads(out_js.read_text())["summary"]["jobs_seen"] == \
        report["summary"]["jobs_seen"]


# ----------------------------------------------------------------------
# Satellite publishers: serving pool + combo caches
# ----------------------------------------------------------------------
def test_replica_pool_publishes_to_registry():
    reg = MetricRegistry()
    pool = ReplicaPool([ReplicaSpec("a", capability=1.0,
                                    cost_per_1k_tokens=2.0)],
                       LeastLoadedRouter())
    pool.route(ServeRequest(uid=0, qclass=DEFAULT_QUERY_CLASSES[0],
                            arrival_s=10.0, prompt_tokens=64,
                            output_tokens=16))
    pool.bind_registry(reg, name="edge")
    text = reg.expose_text()
    assert 'serving_replicas{pool="edge"} 1' in text
    assert "serving_observed_rps" in text
    assert "serving_replica_demand" in text


def test_combo_cache_stats_reach_registry():
    cache = ComboCache("obs-test-cache")
    assert cache.get("k") is None          # miss
    cache.put("k", 1)
    assert cache.get("k") == 1             # hit
    st = cache_stats()["obs-test-cache"]
    assert st == {"hits": 1, "misses": 1, "size": 1}
    tel = Telemetry()
    text = tel.registry.expose_text()
    assert 'combo_cache_hits{cache="obs-test-cache"} 1' in text
    assert 'combo_cache_misses{cache="obs-test-cache"} 1' in text
    assert 'combo_cache_entries{cache="obs-test-cache"} 1' in text
