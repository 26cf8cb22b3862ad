"""Find the knee of an open-loop cell: run it at several arrival rates in
one process and print, for each, the decision latency and whether the
queue grew.

    python3 -m bench.sweep --workload kant-10k.train-steady \\
        --rates 40,50,60,70 --seconds 10 --seed 3

Mean job durations are scaled with the rate, so the GPUs that the
window's own jobs hold stay what the traffic file states.  The queue
grew when a job due in the window was never decided, or when the jobs
due in the window's second half waited more than twice as long (median)
as those of its first half.  The knee is the highest rate at which it
did not; the cell's traffic file states 4/5 of it.  The benchmark's own
runs do not run this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from .spec import load_cell
from .stats import percentile


def grew(win) -> bool:
    lat = win.decision_latencies_s()
    half = win.seconds / 2
    first = [x for u, x in zip(win.window_uids, lat)
             if win.specs[u].due < half]
    second = [x for u, x in zip(win.window_uids, lat)
              if win.specs[u].due >= half]
    if not first or not second:
        return True
    return percentile(second, 50) > 2 * percentile(first, 50)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from .run import SetupError, run_once
    cell = load_cell(args.workload)
    base_rate = cell.traffic["rate_per_s"]
    base_dur = cell.traffic["mean_duration_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate,
                       mean_duration_s=base_dur * base_rate / rate)
        windows = []
        try:
            r = run_once(dataclasses.replace(cell, traffic=traffic),
                         args.seed, args.seconds, False, windows=windows)
        except SetupError as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        lat = windows[0].decision_latencies_s()
        print(json.dumps({
            "rate_per_s": rate, "attempted": r["attempted"],
            "failed": r["failed"], "correct": r["correct"],
            "grew": bool(r["failed"]) or grew(windows[0]),
            "p50_ms": 1e3 * percentile(lat, 50),
            "p95_ms": 1e3 * percentile(lat, 95)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
