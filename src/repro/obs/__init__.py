"""Unified telemetry: metric registry, tracing, decision audit, reports.

The observability layer for the whole stack (core QSCH/RSCH cycles,
dynamics, federation members, serving pools, elastic reshapes).  Four
pillars, one attach point:

* :mod:`repro.obs.registry`  — Prometheus-style metrics with
  ring-buffered time series and text/JSON exposition;
* :mod:`repro.obs.trace`     — Chrome trace-event tracer (Perfetto):
  wall-clock phase spans at their real offsets (also
  ``jax.profiler.TraceAnnotation``s on the profiler's clock), sim-time
  job lifecycle spans, cluster instants, byte counters;
* :mod:`repro.obs.audit`     — kube-scheduler-style decision audit
  (filter eliminations, per-ScorePlugin breakdown of bound nodes,
  preemption rationale) behind the ObserverPlugin extension point;
* :mod:`repro.obs.report`    — ``python -m repro.obs.report`` bundle
  renderer (markdown / JSON).

Telemetry is strictly opt-in: with nothing attached, every core hook
is a ``None`` check and scheduling output is byte-identical to an
untelemetered build (``benchmarks/obs_bench.py`` gates this).  What
the attached spans cost is measured on the chip: PERF.md records the
traced per-layer deltas against the parent commit.

See ``docs/observability.md``.
"""

from ..core.framework.api import ObserverPlugin
from .audit import (DecisionAudit, FilterStat, PassAudit,
                    PlacementDecision, PreemptionRecord, ScoreBreakdown,
                    build_decision)
from .registry import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                       Metric, MetricRegistry)
from .report import build_report, render_markdown
from .telemetry import CycleSpan, JobRecord, Telemetry
from .trace import PID_CLUSTER, PID_JOBS, PID_SCHED, Tracer

__all__ = [
    "Telemetry", "CycleSpan", "JobRecord",
    "MetricRegistry", "Counter", "Gauge", "Histogram", "Metric",
    "DEFAULT_BUCKETS",
    "Tracer", "PID_SCHED", "PID_JOBS", "PID_CLUSTER",
    "ObserverPlugin", "DecisionAudit", "PlacementDecision", "PassAudit",
    "FilterStat", "ScoreBreakdown", "PreemptionRecord", "build_decision",
    "build_report", "render_markdown",
]
