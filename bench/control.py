"""The control for ``correct``: the reference's score pass, put in the
kernel's place and computed one precision below what the configuration
states (bfloat16 for its float32 scores).

    python3 -m bench.control --workload kant-10k.train-steady \\
        --seeds 11,12,13 --seconds 10

runs the cell once per seed with the control planted, in one process,
and prints the numbers the check compares; the control has to come out
as not correct.  The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import ml_dtypes

from .reference import fused_scores, pod_slots


def bf16_scores_and_slots(free, used, mask, group_load, topo_pref, *,
                          request: int, gpus_per_node: int, weights,
                          backend: str = "pallas", **_):
    """Drop-in for ``repro.kernels.ops.node_scores_and_slots``: the
    reference's pass with every operation rounded to bfloat16.  It runs
    on the host, where each rounding happens as written (a compiler
    that may keep excess precision, as XLA on the TPU does, would
    compute it in float32)."""
    w = {"used": weights.used, "fit": weights.fit, "group": weights.group,
         "topo": weights.topo}
    scores = fused_scores(free, used, mask, group_load, topo_pref,
                          request, gpus_per_node, w,
                          dtype=ml_dtypes.bfloat16)
    return scores, pod_slots(free, mask, request)


def plant_control(state, rsch, qsch, calls) -> None:
    calls.impl = bf16_scores_and_slots


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from .run import SetupError, run_once
    from .spec import load_cell
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = run_once(dataclasses.replace(cell), seed, args.seconds,
                         False, plant=plant_control)
        except SetupError as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "checks": {k: c["value"] for k, c in
                                     r["checks"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
