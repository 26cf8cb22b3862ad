"""Drive the system under test through one measured window.

The window runs the program's own path: ``Simulator`` event dispatch ->
``QSCH.cycle`` (Backfill) -> ``RSCH.schedule`` -> the score call in
``repro.kernels.ops`` -> the Pallas kernel.  The driver only decides
*when* events happen:

* ``open_loop``: jobs are due on the wall clock, whatever the scheduler
  does.  The simulated clock is the wall clock in seconds from the
  window's start; a cycle runs whenever anything is queued, back to
  back, as a live scheduler does.  A job's decision latency runs from
  its due time to the end of the first cycle that decided it, placed or
  found not to fit.
* ``backlog``: before each cycle ``jobs_per_cycle`` jobs join the queue
  and the simulated clock moves on by ``sim_seconds_per_cycle``; the
  wall clock plays no part in what is decided, so one seed gives one
  sequence of placements.

Instrumentation is the benchmark's own and sits around the calls into
each layer: the cycle (the TICK dispatch), ``QSCH.try_place`` (which
job was decided, and how), ``RSCH.schedule`` (timed), the module
attribute ``repro.kernels.ops.node_scores_and_slots`` (the score call,
timed until scores and slots are numpy arrays on the host, and a seeded
sample of its inputs and outputs kept for the check), and, in a traced
run, an observer on the program's phase boundaries.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from .cluster import seed_seq
from .traffic import JobStream, JobSpec

FAR = 1e12            # simulated time that never comes within a run


# ---------------------------------------------------------------------------
# Instrumentation
# ---------------------------------------------------------------------------
class _Annotation:
    """A ``jax.profiler.TraceAnnotation`` when tracing, else nothing."""

    def __init__(self, name: str, on: bool) -> None:
        self.name, self.on, self.ann = name, on, None

    def __enter__(self):
        if self.on:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
            self.ann = None


class _PhaseTimer:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: "Spans", name: str) -> None:
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
            self.ann = None
        sp = self.spans
        sp.seconds[self.name] = sp.seconds.get(self.name, 0.0) + dt


class Spans:
    """Observer on QSCH/RSCH's phase boundaries (``obs_phase`` call
    sites: snapshot, queue-sort, preempt, filter, score, reserve-permit,
    bind).  Attached in traced runs only; without it the program runs
    its detached path."""

    audit_on = False

    def __init__(self, annotate: bool) -> None:
        self.annotate = annotate
        self.seconds: Dict[str, float] = {}
        self._timers: Dict[str, _PhaseTimer] = {}

    def reset(self) -> None:
        self.seconds.clear()

    def phase(self, name: str) -> _PhaseTimer:
        t = self._timers.get(name)
        if t is None:
            t = self._timers[name] = _PhaseTimer(self, name)
        return t

    def cycle_begin(self, now: float) -> None:
        pass

    def cycle_end(self, result, ctx) -> None:
        pass

    def emit_bind(self, job, sched, ctx) -> None:
        pass

    def emit_reject(self, job, sched, ctx, reason: str) -> None:
        pass

    def emit_preempt(self, victim, ctx, source) -> None:
        pass


@dataclasses.dataclass
class Capture:
    """One score call kept for the check: its inputs and outputs."""
    index: int
    request: int
    free: np.ndarray
    used: np.ndarray
    mask: np.ndarray
    group_load: np.ndarray
    topo_pref: np.ndarray
    scores: np.ndarray
    slots: np.ndarray


class ScoreCalls:
    """Wrapper of ``repro.kernels.ops.node_scores_and_slots``.  RSCH
    imports that attribute at call time, so replacing it reaches every
    call the scheduler makes.  ``impl`` is what the wrapper calls: the
    program's function, or a stand-in when a control or a fault is
    planted."""

    def __init__(self, impl, annotate: bool, seed: int, stride: int,
                 big_pods: int, max_captures: int) -> None:
        self.impl = impl
        self.annotate = annotate
        rng = seed_seq(seed, 0x636b)                 # "ck"
        self.stride = max(1, int(stride))
        self.offset = int(rng.integers(self.stride))
        self.big_pods = big_pods
        self.max_captures = max_captures
        self.job_pods = 0               # n_pods of the job being placed
        self.reset()

    def reset(self) -> None:
        self.calls = 0
        self.nodes = 0
        self.seconds = 0.0
        self.big_taken = 0
        self.captures: Dict[int, Capture] = {}

    def _want(self, i: int) -> bool:
        if len(self.captures) >= self.max_captures:
            return False
        if i % self.stride == self.offset:
            return True
        if self.job_pods >= self.big_pods and self.big_taken < 4:
            self.big_taken += 1
            return True
        return False

    def __call__(self, free, used, mask, group_load, topo_pref, **kw):
        i = self.calls
        keep = self._want(i)
        if keep:
            free_c, used_c = np.array(free), np.array(used)
        ann = _Annotation("score_call", self.annotate)
        t0 = time.perf_counter()
        with ann:
            s, sl = self.impl(free, used, mask, group_load, topo_pref,
                              **kw)
            s, sl = np.asarray(s), np.asarray(sl)
        self.seconds += time.perf_counter() - t0
        self.calls += 1
        self.nodes += int(np.shape(free)[0])
        if keep:
            self.captures[i] = Capture(
                index=i, request=int(kw["request"]), free=free_c,
                used=used_c, mask=np.asarray(mask), group_load=np.asarray(
                    group_load), topo_pref=np.asarray(topo_pref),
                scores=s, slots=sl)
        return s, sl


# ---------------------------------------------------------------------------
# The log the reference replays
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cycle:
    now: float                       # simulated time of the cycle
    t_end: float                     # wall clock (perf_counter)
    in_window: bool
    # (uid, pods or None, score-call index or -1) per decision, in order
    attempts: List[tuple] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    kind: str
    t0: float = 0.0                  # window start (perf_counter)
    deadline: float = 0.0
    t_close: float = 0.0             # end of the last cycle in the window
    seconds: float = 0.0
    events: List[tuple] = dataclasses.field(default_factory=list)
    cycles: List[Cycle] = dataclasses.field(default_factory=list)
    specs: Dict[int, JobSpec] = dataclasses.field(default_factory=dict)
    decided_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    pacer_lag: List[float] = dataclasses.field(default_factory=list)
    window_uids: List[int] = dataclasses.field(default_factory=list)
    t_stop: float = 0.0              # when the driver stopped
    preemptions: int = 0
    requeues: int = 0
    infeasible: int = 0
    # Summed over the window's cycles: phase seconds (traced runs),
    # score calls, whole cycles and RSCH.schedule calls.
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    call_s: float = 0.0
    calls: int = 0
    cycle_s: float = 0.0             # whole cycles (TICK dispatch)
    rsch_s: float = 0.0              # RSCH.schedule calls
    rsch_calls: int = 0

    @property
    def window_cycles(self) -> List[Cycle]:
        return [c for c in self.cycles if c.in_window]

    def placed_in_window(self) -> List[tuple]:
        return [a for c in self.window_cycles for a in c.attempts
                if a[1] is not None]

    def decision_latencies_s(self) -> List[float]:
        """Every job due in the window: due -> first decision; a job
        that none came for counts the whole wait until the driver
        stopped."""
        out = []
        for uid in self.window_uids:
            t = self.decided_at.get(uid, self.t_stop)
            out.append(t - (self.t0 + self.specs[uid].due))
        return out


class Driver:
    """Builds the simulator around the program and runs the window."""

    def __init__(self, state, qsch, traffic: dict, config: dict,
                 seed: int, calls: ScoreCalls,
                 spans: Optional[Spans] = None) -> None:
        from repro.core import SimConfig, Simulator
        self.state, self.qsch = state, qsch
        self.traffic, self.config = traffic, config
        self.calls, self.spans = calls, spans
        self.sim = Simulator(state, qsch, SimConfig(
            tick_interval=FAR, sample_interval=FAR, binding_latency=0.0))
        self.stream = JobStream(traffic, config["gpus_per_node"], seed)
        self.next_job = 0
        self.win = Window(kind=traffic["arrival"])
        self._cycle: Optional[Cycle] = None
        self.rsch_s, self.rsch_calls = 0.0, 0
        self._wrap_try_place()
        self._wrap_schedule()

    # -- hooks ---------------------------------------------------------
    def _wrap_try_place(self) -> None:
        qsch, calls = self.qsch, self.calls
        orig = qsch.try_place

        def try_place(job, ctx, backfilled=False):
            before = calls.calls
            calls.job_pods = job.n_pods
            ok = orig(job, ctx, backfilled)
            call = before if calls.calls == before + 1 else -1
            pods = None
            if ok:
                pods = tuple((p.node, tuple(p.gpu_indices))
                             for p in job.placement.pods)
            if self._cycle is not None:
                self._cycle.attempts.append((job.uid, pods, call))
            return ok

        qsch.try_place = try_place

    def _wrap_schedule(self) -> None:
        """Time every ``RSCH.schedule`` call (QSCH calls it through the
        instance): the whole of RSCH, level-1 group choice included."""
        rsch = self.qsch.rsch
        orig = rsch.schedule
        annotate = self.calls.annotate

        def schedule(job, snap, ctx=None):
            t0 = time.perf_counter()
            with _Annotation("rsch", annotate):
                res = orig(job, snap, ctx)
            self.rsch_s += time.perf_counter() - t0
            self.rsch_calls += 1
            return res

        rsch.schedule = schedule

    def _job(self, spec: JobSpec, submit_time: float):
        from repro.core import Job, JobKind
        tr = self.traffic
        return Job(uid=spec.uid, tenant=tr["tenant"],
                   gpu_type=self.config["gpu_type"], n_pods=spec.n_pods,
                   gpus_per_pod=spec.gpus_per_pod, kind=JobKind.TRAIN,
                   gang=True, priority=int(tr["priority"]),
                   submit_time=submit_time, duration=spec.duration)

    def _submit(self, spec: JobSpec, submit_time: float) -> None:
        from repro.core import EventKind
        self.win.specs[spec.uid] = spec
        self.win.events.append(("submit", spec.uid, submit_time))
        self.sim.bus.push(submit_time, EventKind.SUBMIT,
                          self._job(spec, submit_time))

    def _dispatch_until(self, now: float) -> None:
        from repro.core import EventKind, JobState
        bus, sim = self.sim.bus, self.sim
        while len(bus) and bus.peek().t <= now:
            ev = bus.pop()
            sim.now = ev.t
            ended = (ev.kind is EventKind.END
                     and ev.payload.state is JobState.RUNNING)
            bus.dispatch(ev)
            if ended and ev.payload.state is JobState.COMPLETED:
                self.win.events.append(("end", ev.payload.uid))

    def _run_cycle(self, now: float, in_window: bool) -> Cycle:
        from repro.core import EventKind
        sim, bus = self.sim, self.sim.bus
        cyc = Cycle(now=now, t_end=0.0, in_window=in_window)
        self._cycle = cyc
        t_start = time.perf_counter()
        p0, r0, i0 = sim.preemptions, sim.requeues, sim.infeasible
        sp, cs = self.spans, self.calls
        if in_window:
            s0, c0 = cs.seconds, cs.calls
            rs0, rn0 = self.rsch_s, self.rsch_calls
            if sp is not None:
                ph0 = dict(sp.seconds)
        ev = bus.push(now, EventKind.TICK)
        popped = bus.pop()
        if popped is not ev:
            raise RuntimeError("an event due before the cycle was left "
                               "undispatched")
        sim.now = now
        with _Annotation("cycle", self.calls.annotate):
            bus.dispatch(ev)
        cyc.t_end = time.perf_counter()
        self._cycle = None
        w = self.win
        w.preemptions += sim.preemptions - p0
        if in_window:
            w.requeues += sim.requeues - r0
            w.infeasible += sim.infeasible - i0
            w.call_s += cs.seconds - s0
            w.calls += cs.calls - c0
            w.cycle_s += cyc.t_end - t_start
            w.rsch_s += self.rsch_s - rs0
            w.rsch_calls += self.rsch_calls - rn0
            if sp is not None:
                for k, v in sp.seconds.items():
                    w.phase_s[k] = w.phase_s.get(k, 0.0) + v - ph0.get(k, 0.0)
        w.cycles.append(cyc)
        w.events.append(("cycle", len(w.cycles) - 1))
        for uid, _, _ in cyc.attempts:
            w.decided_at.setdefault(uid, cyc.t_end)
        return cyc

    # -- windows -------------------------------------------------------
    def run(self, seconds: float) -> Window:
        if self.win.kind == "open_loop":
            return self._open_loop(seconds)
        return self._backlog(seconds)

    def _open_loop(self, seconds: float) -> Window:
        w, stream, qsch = self.win, self.stream, self.qsch
        grace = float(self.traffic["grace_s"])
        w.seconds = seconds
        w.t0 = t0 = time.perf_counter()
        w.deadline = deadline = t0 + seconds
        hard_end = deadline + grace
        undecided = set()
        last_cycle_end = t0
        annotate = self.calls.annotate
        while True:
            tw = time.perf_counter()
            now = tw - t0
            if undecided:
                undecided = {u for u in undecided if u not in w.decided_at}
            if tw >= deadline and (not undecided or tw >= hard_end):
                break
            while stream[self.next_job].due <= now:
                spec = stream[self.next_job]
                self.next_job += 1
                self._submit(spec, spec.due)
                if spec.due < seconds:
                    w.window_uids.append(spec.uid)
                    undecided.add(spec.uid)
                    # How late the generator handed the job over: after
                    # its due time, or after the cycle that was running
                    # then, whichever came later.
                    w.pacer_lag.append(
                        tw - max(t0 + spec.due, last_cycle_end))
            self._dispatch_until(now)
            if qsch.queue_depth():
                cyc = self._run_cycle(now, in_window=tw < deadline)
                last_cycle_end = cyc.t_end
                if tw < deadline:
                    w.t_close = cyc.t_end
                continue
            nxt = stream[self.next_job].due
            head = self.sim.bus.peek()
            if head is not None:
                nxt = min(nxt, head.t)
            wait = t0 + nxt - time.perf_counter()
            if wait > 0:
                with _Annotation("pacer_idle", annotate):
                    time.sleep(wait)
        w.t_stop = time.perf_counter()
        return w

    def _backlog(self, seconds: float) -> Window:
        w, stream = self.win, self.stream
        per_cycle = int(self.traffic["jobs_per_cycle"])
        dt = float(self.traffic["sim_seconds_per_cycle"])
        w.seconds = seconds
        w.t0 = t0 = time.perf_counter()
        w.deadline = deadline = t0 + seconds
        k = 0
        while time.perf_counter() < deadline:
            now = k * dt
            for _ in range(per_cycle):
                spec = stream[self.next_job]
                self.next_job += 1
                self._submit(spec, now)
            self._dispatch_until(now)
            cyc = self._run_cycle(now, in_window=True)
            w.t_close = cyc.t_end
            k += 1
        w.t_stop = time.perf_counter()
        return w
