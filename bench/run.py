"""Run one cell once and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The program under test is taken from ``<checkout>/src``.  Set-up builds
the cluster and the traffic from the seed, warms every shape the cell's
traffic uses (one score-call variant per pod size) and counts as
``setup_s``; the window then runs for ``--seconds``; the reference
checks what the window decided once it has closed and the program's
state is freed.  With ``--trace 1`` the window runs under the JAX
profiler with the phase observer attached and the per-layer metrics are
printed instead of the end-to-end ones.

Standard error ends with each number compared beside its limit; the
last line of standard output is the JSON result.  Without a TPU, or
with fewer chips than the cell asks for, the command exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Callable, Optional

from .spec import ROOT, Cell, load_cell

CACHE_DIR = ROOT / ".bench_cache" / "jax"
HOST_SPANS = ("cycle", "rsch", "score_call", "pacer_idle", "snapshot",
              "queue-sort", "preempt", "filter", "score", "reserve-permit",
              "bind")


class SetupError(RuntimeError):
    """The run cannot be measured here: no result is printed."""


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def import_program():
    """Put ``<checkout>/src`` first on the path and check that the
    program comes from there."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.core
    except ImportError as e:
        raise SetupError(f"the program is not in {src}: {e}") from None
    where = os.path.abspath(repro.core.__file__)
    if not where.startswith(src + os.sep):
        raise SetupError(f"repro imported from {where}, not {src}")


class CompileCounter:
    """Backend compiles and persistent-cache loads, from JAX's
    monitoring events; registered once per process."""

    _instance = None

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.events = 0

        def on_duration(event: str, secs: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.events += 1

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.events += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = CompileCounter()
        return cls._instance


def enable_compile_cache() -> str:
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(CACHE_DIR)
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SetupError(f"needs {chips} chips, JAX found {len(devs)}")
    return devs


class Run:
    """What the metric readers (``bench/metrics/*.py``) read."""

    def __init__(self, cell: Cell, win, calls, spans, trace, peaks,
                 setup_s: float) -> None:
        self.cell, self.win, self.calls, self.spans = cell, win, calls, spans
        self.trace, self.peaks, self.setup_s = trace, peaks, setup_s


def warm_up(rsch, qsch, state, traffic: dict, config: dict) -> None:
    """Every shape the window uses: the first snapshot, and one
    placement attempt (pure: nothing is committed) per pod size of the
    mix, plus a multi-group gang when the mix has multi-node jobs."""
    from repro.core import Job, JobKind
    from .traffic import pod_shape, pod_sizes
    snap = qsch.snapshotter.take(state)
    g = config["gpus_per_node"]
    shapes = {(1, r) for r in pod_sizes(traffic, g)}
    big = max(p["gpus"] for p in traffic["population"] if p["per_block"])
    if big > g:
        shapes.add(pod_shape(big, g))
    for k, (n_pods, per_pod) in enumerate(sorted(shapes)):
        rsch.schedule(Job(uid=-1 - k, tenant=traffic["tenant"],
                          gpu_type=config["gpu_type"], n_pods=n_pods,
                          gpus_per_pod=per_pod, kind=JobKind.TRAIN), snap)


def run_once(cell: Cell, seed: int, seconds: float, trace: bool, *,
             on_chip: bool = True, backend: Optional[str] = None,
             plant: Optional[Callable] = None, log=None,
             windows: Optional[list] = None) -> dict:
    """One run of ``cell``.  ``on_chip=False`` skips the look for a
    chip and the compile cache (CPU tests); ``backend`` replaces the
    configuration's score backend; ``plant(state, rsch, qsch, calls)``
    changes the system under test before set-up (controls and faults);
    ``windows``, where given, receives the window's log.  Returns the
    result dict; raises SetupError when nothing can be measured."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    import_program()
    import jax
    from repro.kernels import ops
    from .check import check
    from .cluster import background_busy, build_program
    from .driver import Driver, ScoreCalls, Spans
    from .peaks import peaks_for
    from .traffic import pod_sizes
    from . import trace as tr

    if on_chip:
        devs = find_devices(cell.chips)
        peaks = peaks_for(devs[0].device_kind)
        log(f"[bench] compile cache: {enable_compile_cache()}")
    else:
        devs, peaks = jax.devices(), None
    counter = CompileCounter.get()
    config, traffic = cell.config, cell.traffic

    t_build = time.perf_counter()
    busy = background_busy(config, seed)
    state, rsch, qsch = build_program(
        config, busy, backend or config["score_backend"])
    calls = ScoreCalls(ops.node_scores_and_slots, annotate=trace,
                       seed=seed, stride=traffic["check_every_calls"],
                       big_pods=traffic["check_gang_pods"],
                       max_captures=traffic["check_max_calls"])
    spans = Spans(annotate=True) if trace else None
    if spans is not None:
        qsch.obs = rsch.obs = spans
    orig_call = ops.node_scores_and_slots
    ops.node_scores_and_slots = calls
    try:
        if plant is not None:
            plant(state, rsch, qsch, calls)
        driver = Driver(state, qsch, traffic, config, seed, calls, spans)
        t_warm = time.perf_counter()
        c_warm = counter.events
        warm_up(rsch, qsch, state, traffic, config)
        log(f"[bench] setup: build_s={t_warm - t_build:.3f} warm_s="
            f"{time.perf_counter() - t_warm:.3f} warm_compiles="
            f"{counter.events - c_warm}")
        calls.reset()
        if spans is not None:
            spans.reset()
        c0 = counter.events
        setup_s = process_age_s()
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        error = None
        try:
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN) \
                        if trace else contextlib.nullcontext():
                    win = driver.run(seconds)
            finally:
                if trace:
                    jax.profiler.stop_trace()
        except Exception:
            error = traceback.format_exc()
            log(error)
            win = driver.win
        in_window = counter.events - c0
        stats = devs[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        final_busy = state.gpu_busy.copy()
    finally:
        ops.node_scores_and_slots = orig_call
    del driver, state, rsch, qsch
    gc.collect()
    if windows is not None:
        windows.append(win)

    summary = None
    if trace:
        summary = tr.summarize(tr.load(tmp, HOST_SPANS))
        shutil.rmtree(tmp, ignore_errors=True)
    checks, ref_s = check(config, traffic, seed, win, calls.captures,
                          final_busy, pod_sizes(traffic,
                                                config["gpus_per_node"]))
    run = Run(cell, win, calls, spans, summary, peaks, setup_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    attempts = sum(len(c.attempts) for c in win.window_cycles)
    placed = len(win.placed_in_window())
    log(f"[bench] {cell.name} seed={seed} window_s="
        f"{win.t_close - win.t0:.3f} cycles={len(win.window_cycles)} "
        f"attempts={attempts} placed={placed} "
        f"no_room_share={(attempts - placed) / max(attempts, 1):.4f} "
        f"score_calls={calls.calls} checked_calls={len(calls.captures)} "
        f"reference_s={ref_s:.3f}")
    print(f"[bench] compiles_in_window={in_window}", flush=True)
    correct = error is None and all(v <= lim for v, lim in checks.values())
    if win.kind == "open_loop":
        attempted = len(win.window_uids)
        failed = sum(1 for u in win.window_uids if u not in win.decided_at)
    else:
        attempted = len({a[0] for c in win.window_cycles
                         for a in c.attempts})
        failed = 0
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    if error is not None:
        result["checks"]["window_error"] = {"value": 1, "limit": 0}
    return result


def main(argv=None) -> int:
    t_main = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        result = run_once(cell, args.seed, args.seconds, bool(args.trace))
    except (SetupError, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"[bench] run_s={time.perf_counter() - t_main:.1f}",
          file=sys.stderr, flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
