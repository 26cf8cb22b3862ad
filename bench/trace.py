"""From a profiler trace to the numbers the device-trace metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event records; everything after that works on those records, so
a small recorded trace (``tests/bench/data/``) checks the reduction
without a chip.

* device busy time: the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:`` plane),
  inside the traced window, averaged over the devices;
* kernel time: the summed durations of the operations that belong to a
  kernel, found by name in the event name or its ``hlo_op`` /
  ``hlo_module`` / ``long_name`` stats;
* idle gaps: each stretch in the window in which no device operation
  ran, charged to the innermost host span (``jax.profiler.
  TraceAnnotation``) that was open at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence

from .stats import union_length

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench_window"
NAME_STATS = ("hlo_op", "hlo_module", "long_name")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, str]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def from_rows(rows: Iterable[list]) -> List[Event]:
    return [Event(*r) for r in rows]


def load(log_dir: str, host_spans: Sequence[str]) -> List[Event]:
    """Device operations, and the host spans named in ``host_spans``,
    of the one trace under ``log_dir``."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    wanted = set(host_spans) | {WINDOW_SPAN}
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if host and e.name not in wanted:
                    continue
                stats = {}
                if device:
                    stats = {k: str(v) for k, v in e.stats
                             if k in NAME_STATS}
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 stats))
    return out


def window(events: Sequence[Event]) -> tuple:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, List[Event]]:
    """Device operations inside [lo, hi], clipped, per device plane."""
    out: Dict[str, List[Event]] = {}
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.setdefault(e.plane, []).append(
                dataclasses.replace(e, start_ns=s, dur_ns=t - s))
    return out


def busy_ns(ops: Dict[str, List[Event]]) -> float:
    """Busy time averaged over the devices that ran anything."""
    if not ops:
        return 0.0
    return sum(union_length([(e.start_ns, e.end_ns) for e in evs])
               for evs in ops.values()) / len(ops)


def matches(e: Event, key: str) -> bool:
    return key in e.name or any(key in v for v in e.stats.values())


def kernel_ns(ops: Dict[str, List[Event]], key: str) -> float:
    return sum(e.dur_ns for evs in ops.values() for e in evs
               if matches(e, key))


def op_name(e: Event) -> str:
    """An operation's short name: its HLO instruction, without the text
    of its shapes and operands."""
    return e.stats.get("hlo_op") or e.name.split(" = ", 1)[0]


def top_ops(ops: Dict[str, List[Event]], k: int = 10) -> List[list]:
    """The ``k`` operation names that took most device time, seconds."""
    tot: Dict[str, float] = {}
    for evs in ops.values():
        for e in evs:
            name = op_name(e)
            tot[name] = tot.get(name, 0.0) + e.dur_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def idle_gaps(events: Sequence[Event], ops: Dict[str, List[Event]],
              lo: float, hi: float, k: int = 10) -> List[list]:
    """Device idle time in [lo, hi], summed by the innermost host span
    open at each gap's middle; the ``k`` largest, seconds.  Uses the
    first device plane."""
    if not ops:
        return [["(no device operation)", (hi - lo) * 1e-9]]
    evs = sorted(next(iter(ops.values())), key=lambda e: e.start_ns)
    gaps, cur = [], lo
    for e in evs:
        if e.start_ns > cur:
            gaps.append((cur, e.start_ns))
        cur = max(cur, e.end_ns)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name != WINDOW_SPAN and e.dur_ns > 0]
    # Sweep: span starts and ends and gap middles in time order; spans
    # of one thread nest, so the innermost open one is the top.
    marks = []
    for i, s in enumerate(spans):
        marks.append((s.start_ns, 1, i))
        marks.append((s.end_ns, 0, i))
    for j, (a, b) in enumerate(gaps):
        marks.append(((a + b) / 2, 2, j))
    marks.sort()
    open_: List[int] = []
    tot: Dict[str, float] = {}
    for _, kind, i in marks:
        if kind == 1:
            open_.append(i)
        elif kind == 0:
            open_.remove(i)
        else:
            a, b = gaps[i]
            name = spans[open_[-1]].name if open_ else "(no host span)"
            tot[name] = tot.get(name, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    ops: Dict[str, List[Event]]     # device operations in the window
    device_ops: List[list]
    idle_gaps: List[list]

    def kernel_s(self, key: str) -> float:
        return kernel_ns(self.ops, key) * 1e-9


def summarize(events: Sequence[Event]) -> Summary:
    lo, hi = window(events)
    ops = device_ops(events, lo, hi)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns(ops) * 1e-9,
                   ops=ops, device_ops=top_ops(ops),
                   idle_gaps=idle_gaps(events, ops, lo, hi))
