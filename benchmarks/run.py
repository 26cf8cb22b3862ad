"""Benchmark orchestrator: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` runs everything except the
(hour-scale) dry-run sweeps, which are launched separately via
``python -m repro.launch.dryrun`` and only *read* here by the roofline
table.

``--seed N`` threads one seed through every stochastic benchmark (via
``benchmarks.common.bench_seed``), making runs reproducible
run-to-run; ``--only SUBSTR`` filters modules by name; ``--list``
prints the registered benchmark names and exits (the names ``--only``
matches against); ``--ci-smoke`` runs exactly the gated subset CI
runs, each module with its smoke flags, so one orchestrator line
replaces a per-bench workflow step and ``--json`` captures the whole
gate matrix in one artifact."""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

# (title, module, ci_smoke_argv) — ci_smoke_argv is None for modules
# excluded from the CI gate run (paper-figure sweeps, artifact readers)
# and the module's smoke argv otherwise ([] = run with defaults).
MODULES = [
    ("Fig 2   job distribution", "benchmarks.fig2_job_distribution",
     None),
    ("Fig 3   Backfill GAR/SOR", "benchmarks.fig3_backfill_gar_sor",
     None),
    ("Fig 4   JWTD by policy", "benchmarks.fig4_jwtd_policies", None),
    ("Fig 5   Backfill GFR", "benchmarks.fig5_backfill_gfr", None),
    ("Fig 6   E-Binpack GFR", "benchmarks.fig6_ebinpack_gfr", None),
    ("Fig 7   E-Binpack GAR/SOR", "benchmarks.fig7_ebinpack_gar_sor",
     None),
    ("Fig 8   E-Binpack JWTD", "benchmarks.fig8_ebinpack_jwtd", None),
    ("Fig 9   E-Binpack JTTED", "benchmarks.fig9_ebinpack_jtted", None),
    ("Fig10-12 tenant quotas", "benchmarks.fig10_quota", None),
    ("Fig13-14 inference GAR/GFR", "benchmarks.fig13_inference_gar",
     None),
    ("Fig 15  GFR vs scale", "benchmarks.fig15_gfr_scale", None),
    ("§3.4.3  snapshot bench", "benchmarks.snapshot_bench", []),
    ("§3.4    sched scale bench", "benchmarks.sched_scale_bench",
     ["--smoke"]),
    ("framework plugin bench", "benchmarks.plugin_bench", []),
    ("dynamics bench", "benchmarks.dynamics_bench", ["--smoke"]),
    ("federation bench", "benchmarks.federation_bench", ["--smoke"]),
    ("serving fabric bench", "benchmarks.serving_bench", ["--smoke"]),
    ("elastic training bench", "benchmarks.elastic_bench", ["--smoke"]),
    ("observability bench", "benchmarks.obs_bench", ["--smoke"]),
    ("self-tuning bench", "benchmarks.tuning_bench", ["--smoke"]),
    ("§Roofline table", "benchmarks.roofline", None),
]


def _sanitize(obj):
    """NaN/Inf -> None so the gate summary is strict-JSON parseable."""
    if isinstance(obj, dict):
        return {k: _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_sanitize(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def main(argv=None) -> int:
    import importlib
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0,
                    help="run-wide seed for stochastic benchmarks "
                         "(exported as REPRO_BENCH_SEED)")
    ap.add_argument("--only", default="",
                    help="only run modules whose name contains this")
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmark names and exit")
    ap.add_argument("--ci-smoke", action="store_true",
                    help="run the CI gate subset, each module with its "
                         "smoke flags")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write a machine-readable per-module gate "
                         "summary (ok/seconds/error/artifacts) to PATH")
    args = ap.parse_args(argv)
    if args.list:
        for title, modname, ci in MODULES:
            mark = "ci" if ci is not None else "  "
            print(f"{modname:40s} [{mark}] {title}")
        return 0
    # Exported BEFORE any benchmark module is imported: modules read it
    # through benchmarks.common.bench_seed() at main() time.
    os.environ["REPRO_BENCH_SEED"] = str(args.seed)
    # The orchestrator's flags are its own: a module whose main() parses
    # sys.argv (e.g. dynamics_bench's --smoke) must not choke on
    # --only/--seed, so hide them for the module runs.
    argv0 = sys.argv[:1]
    sys.argv = argv0
    failures = []
    if args.ci_smoke:
        selected = [(t, m, ci) for t, m, ci in MODULES
                    if ci is not None and args.only in m]
    else:
        selected = [(t, m, None) for t, m, ci in MODULES
                    if args.only in m]
    if not selected:
        print(f"--only {args.only!r} matches no benchmark module; "
              f"available: {[m for _, m, _ in MODULES]}")
        return 2
    from benchmarks import common
    records = []
    for title, modname, ci_argv in selected:
        print(f"\n================ {title} ({modname})")
        t0 = time.time()
        n_artifacts = len(common.RECORDED)
        rec = {"module": modname, "title": title, "ok": True,
               "seconds": 0.0, "error": None, "artifacts": []}
        try:
            # A module that parses sys.argv sees exactly its smoke
            # flags in a --ci-smoke run, nothing otherwise.
            sys.argv = argv0 + (ci_argv or [])
            mod = importlib.import_module(modname)
            mod.main()
            print(f"[ok] {title} ({time.time() - t0:.1f}s)")
        except Exception as e:   # noqa: BLE001 — report all, fail at end
            failures.append(title)
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            print(f"[FAIL] {title}: {e}")
            traceback.print_exc()
        rec["seconds"] = round(time.time() - t0, 3)
        rec["artifacts"] = list(common.RECORDED[n_artifacts:])
        records.append(rec)
    print("\n================ summary")
    if args.json:
        payload = _sanitize({
            "seed": args.seed,
            "passed": len(selected) - len(failures),
            "failed": len(failures),
            "modules": records,
        })
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"[json] gate summary -> {os.path.abspath(args.json)}")
    if failures:
        print(f"{len(failures)} benchmark(s) failed: {failures}")
        return 1
    print(f"all {len(selected)} benchmarks passed (seed {args.seed})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
