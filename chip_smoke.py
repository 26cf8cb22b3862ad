"""Smoke run of Kant's main path on one TPU chip.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --four-chips  # a 4-chip host: mesh training only

It drives the system through the entry points a user calls, at sizes
users call real, and checks every result against the repo's own
reference.  Each phase prints one line with the device, the sizes, its
wall seconds and its compiles; the times are those of a smoke run,
compilation included, and are not measurements.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.  Without a TPU the script exits non-zero before any phase.

One-chip phases, in order:

1. score kernel: the ``"pallas"`` score call, lowered through
   ``repro.kernels.ops``, holds a Mosaic ``tpu_custom_call`` (no
   interpret mode);
2. Kant §5 cluster: 10,000 nodes x 8 GPUs, a seeded ``training_trace``
   through ``Simulator`` -> ``QSCH`` -> ``RSCH`` with the default
   profiles (Backfill + E-Binpack), once with ``score_backend="pallas"``
   and once with ``"np"``: every job's start time and pod placement, and
   the GAR/SOR/GFR series, must be identical;
3. one fragmented cycle at 1,000,000 nodes, a 64-pod x 8-GPU gang: the
   ``"pallas"`` picks must equal the ``"np"`` picks;
4. serving: ``repro.launch.serve.serve_demo`` at glm4-9b's published
   widths with the depth cut to ``SERVE_LAYERS``; every request must
   finish, each must equal a solo B=1 run of its prompt, and one
   prompt's prefill logits must match a float32 reference computed
   under ``jax.default_matmul_precision("highest")``.

``--four-chips`` runs only the path that spans chips: a 4-GPU job that
Kant places is trained on a ``job_mesh_shape(4)`` mesh over the 4 chips
with ``param_shardings`` (hymba-1.5b at published widths, depth cut to
``TRAIN_LAYERS``), and its losses and grad norms must match the same
steps on one chip; both sides run under
``jax.default_matmul_precision("highest")``.

The persistent compilation cache is wherever ``JAX_COMPILATION_CACHE_DIR``
points, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# -- sizes -----------------------------------------------------------------
SCHED_NODES = 10_000          # Kant §5: 10k nodes x 8 GPUs = 80k GPUs
SCHED_JOBS = 500
SCHED_RATE_PER_HOUR = 3000.0
BIG_NODES = 1_000_000
SERVE_ARCH = "glm4-9b"
SERVE_LAYERS = 4              # of 40: 2.06 B parameters, 8.2 GB in f32
SERVE_REQUESTS = 8
TRAIN_ARCH = "hymba-1.5b"
TRAIN_LAYERS = 8              # of 32: ~0.5 B parameters
TRAIN_STEPS = 3

# -- tolerances ----------------------------------------------------------
# Prefill logits vs the float32 "highest"-precision reference: the
# engine runs f32 weights at the default matmul precision, which on a
# TPU multiplies in one bf16 pass (unit roundoff 2^-8), so the relative
# L2 error of the logits is bounded by a few bf16 ulps.
LOGITS_REL_L2 = 2e-2
# Sharded vs one-chip training, both under "highest" matmul precision
# (at the default one-pass bf16 the two layouts round differently and
# the grad norms drift apart by percents).  Left to differ is the f32
# reduction order of the sharded matmuls and collectives: on a v5e the
# loss agreed to ~3e-6 and the grad norm to ~1e-3 relative over 3 steps.
TRAIN_LOSS_RTOL = 1e-4
TRAIN_GNORM_RTOL = 5e-3


class CompileCounter:
    """Counts executables built or loaded (backend compiles), in all and
    per jitted function name, their seconds, and how many came from the
    persistent cache."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_name: dict = {}

        def on_duration(event: str, secs: float, fun_name: str = "",
                        **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs
                self.by_name[fun_name] = self.by_name.get(fun_name, 0) + 1

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return self.compiles, self.seconds, self.cache_hits

    def of(self, fn) -> int:
        """Backend compiles of the jitted ``fn`` (named ``jit(<name>)``)."""
        return self.by_name.get(f"jit({fn.__name__})", 0)


class Phase:
    """Times one phase from its creation and prints its line: device,
    sizes, wall seconds, compiles in the phase."""

    def __init__(self, name: str, counter: CompileCounter, device) -> None:
        self.name, self.counter, self.device = name, counter, device
        self.t0 = time.perf_counter()
        self.c0 = counter.snapshot()

    def done(self, **fields) -> None:
        c, s, h = (a - b for a, b in zip(self.counter.snapshot(), self.c0))
        wall = time.perf_counter() - self.t0
        extra = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[smoke] {self.name}: device={self.device.device_kind} "
              f"{extra} wall_s={wall:.3f} compiles={c} "
              f"compile_s={s:.3f} cache_hits={h}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


# ---------------------------------------------------------------------------
# Scheduler phases
# ---------------------------------------------------------------------------
def kernel_lowering(backend: str = "pallas") -> int:
    """Lower the score call's one program on the tables RSCH's call
    stages; return the number of Mosaic custom calls in it."""
    from repro.core.scoring import E_BINPACK
    from repro.kernels import ops

    n = SCHED_NODES
    cols = (np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int32), np.zeros(n, np.float32),
            np.zeros(n, np.float32))
    tables = ops.stage_tables(cols, n)
    w = E_BINPACK
    lowered = ops.scores_slots_program.lower(
        *tables, n=n, request=8, gpus_per_node=8, w_used=w.used,
        w_fit=w.fit, w_group=w.group, w_topo=w.topo,
        interpret=(backend != "pallas"))
    return lowered.as_text().count("tpu_custom_call")


def simulate(backend: str, n_nodes: int, n_jobs: int, seed: int):
    from benchmarks.sched_scale_bench import make_topology
    from repro.core import (ClusterState, QSCH, QuotaManager, RSCH,
                            RSCHConfig, Simulator, SimConfig,
                            default_profiles, training_trace)
    topo = make_topology(n_nodes)
    state = ClusterState.create(topo)
    rsch = RSCH(topo, RSCHConfig(score_backend=backend),
                profiles=default_profiles())
    qsch = QSCH(QuotaManager({"t0": {0: 10 ** 9}}), rsch)
    jobs = training_trace(n_jobs, seed=seed,
                          arrival_rate_per_hour=SCHED_RATE_PER_HOUR)
    return Simulator(state, qsch, SimConfig()).run(jobs)


def trace_identity(n_nodes: int, n_jobs: int, seed: int,
                   counter: CompileCounter,
                   backend: str = "pallas") -> dict:
    """Run one trace with ``backend`` and with ``"np"``; fail unless
    placements and metric series are identical."""
    from benchmarks.sched_scale_bench import _placement_key
    from repro.kernels.ops import scores_slots_program as kernel

    variants0 = counter.of(kernel)
    dev = simulate(backend, n_nodes, n_jobs, seed)
    variants = counter.of(kernel) - variants0
    ref = simulate("np", n_nodes, n_jobs, seed)
    a, b = _placement_key(dev.jobs), _placement_key(ref.jobs)
    if a != b:
        first = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
        fail(f"{backend} placements differ from np at job {a[first][0]}:"
             f" {a[first]} vs {b[first]}")
    for series in ("gar_series", "gfr_series"):
        for x, y in zip(getattr(dev.metrics, series)(),
                        getattr(ref.metrics, series)()):
            if not np.array_equal(x, y):
                fail(f"{series} differs between {backend} and np")
    if dev.metrics.sor() != ref.metrics.sor():
        fail(f"SOR differs: {dev.metrics.sor()} vs {ref.metrics.sor()}")
    rep = ref.metrics.report()
    placed = sum(j.placement is not None for j in ref.jobs)
    return {"jobs": len(ref.jobs), "placed": placed,
            "cycles": dev.cycles, "kernel_variants": variants,
            "median_gar": rep["median_gar"], "sor": rep["sor"],
            "mean_gfr": rep["mean_gfr"]}


def big_cycle(n_nodes: int, backend: str = "pallas") -> dict:
    """One fragmented cycle: a 64-pod x 8-GPU gang; the backend's picks
    must equal numpy's, and so must the fused score of every node, bit
    for bit, on this cluster's columns (the slot walk breaks ties
    exactly, so one ulp can move a placement)."""
    from benchmarks.sched_scale_bench import GANG_PODS, GPUS_PER_POD, \
        make_state
    from repro.core import (Job, JobKind, RSCH, RSCHConfig,
                            default_profiles)
    from repro.core.scoring import E_BINPACK, node_scores_np
    from repro.core.snapshot import FullSnapshotter
    from repro.kernels import ops

    state = make_state(n_nodes)
    snap = FullSnapshotter().take(state)
    job = Job(uid=1, tenant="smoke", gpu_type=0, n_pods=GANG_PODS,
              gpus_per_pod=GPUS_PER_POD, kind=JobKind.TRAIN)
    picks = {}
    for b in (backend, "np"):
        rsch = RSCH(state.topology, RSCHConfig(score_backend=b),
                    profiles=default_profiles())
        res = rsch.schedule(job, snap)
        if res.placement is None:
            fail(f"1M-node gang not placed by {b}: {res.reason}")
        picks[b] = [(p.node, tuple(p.gpu_indices))
                    for p in res.placement.pods]
    if picks[backend] != picks["np"]:
        fail(f"{backend} 1M-node picks differ from np")

    rng = np.random.default_rng(0)
    free, used = snap.free_gpus, snap.used_gpus
    mask = np.ones(n_nodes, bool)
    gload = rng.random(n_nodes).astype(np.float32)
    topo = (1.0 / (1.0 + rng.integers(0, 8, n_nodes))).astype(np.float32)
    want = node_scores_np(free, used, mask, gload, topo, GPUS_PER_POD, 8,
                          E_BINPACK)
    got, _ = ops.node_scores_and_slots(
        free, used, mask.astype(np.int32), gload, topo,
        request=GPUS_PER_POD, gpus_per_node=8, weights=E_BINPACK,
        backend=backend)
    diff = np.nonzero(want.view(np.uint32)
                      != np.asarray(got).view(np.uint32))[0]
    if diff.size:
        i = int(diff[0])
        fail(f"score bits differ at {diff.size} nodes; first node {i}: "
             f"np={want[i]!r} {backend}={np.asarray(got)[i]!r} "
             f"free={free[i]} used={used[i]} gload={gload[i]!r} "
             f"topo={topo[i]!r}")
    return {"nodes": n_nodes, "pods": GANG_PODS,
            "gpus_per_pod": GPUS_PER_POD, "score_bits": "identical"}


# ---------------------------------------------------------------------------
# Serving phase
# ---------------------------------------------------------------------------
def serving(arch: str, *, smoke: bool, n_layers, requests: int,
            seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import serve_demo
    from repro.serve import Request, ServeEngine

    finished, engine = serve_demo(arch, smoke=smoke, n_layers=n_layers,
                                  requests=requests, batch_size=4,
                                  max_new=8, seed=seed)
    cfg, params = engine.cfg, engine.params
    if len(finished) != requests:
        fail(f"served {len(finished)}/{requests} requests")
    for r in finished:
        if r.evicted or len(r.generated) != r.max_new_tokens:
            fail(f"request {r.uid} ended with {len(r.generated)}/"
                 f"{r.max_new_tokens} tokens")

    # Solo B=1 references: each prompt alone in a one-slot engine.
    solo_engine = ServeEngine(cfg, params, batch_size=1,
                              max_seq=engine.max_seq)
    for r in sorted(finished, key=lambda r: r.uid):
        solo_engine.submit(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
    solo = {r.uid: r.generated for r in solo_engine.run_until_drained()}
    for r in finished:
        if r.generated != solo[r.uid]:
            fail(f"request {r.uid}: batched tokens {r.generated} != "
                 f"solo {solo[r.uid]}")

    # Prefill logits at the engine's default precision vs the float32
    # reference at "highest", one B=1 prompt (glm4-9b takes tokens only).
    prompt = max(finished, key=lambda r: len(r.prompt)).prompt
    batch = {"tokens": jnp.asarray(prompt[None, :])}

    def prefill(p, b):      # (logits, KV cache), as the engine admits
        return engine.model.prefill(p, b, seq_len=engine.max_seq)

    compiled = jax.jit(prefill).lower(params, batch).compile()
    mem = compiled.memory_analysis()
    got = np.asarray(compiled(params, batch)[0], np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(prefill)(params, batch)[0], np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not np.isfinite(got).all() or rel > LOGITS_REL_L2:
        fail(f"prefill logits rel L2 error {rel:.3e} > {LOGITS_REL_L2}")
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit", 0)
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) if mem is not None else 0
    if limit and need > limit:
        fail(f"prefill needs {need} B of {limit} B on the device")
    return {"arch": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "params": n_params,
            "dtype": str(jax.tree.leaves(params)[0].dtype),
            "requests": len(finished),
            "tokens": sum(len(r.generated) for r in finished),
            "prompt_lens": sorted({len(r.prompt) for r in finished}),
            "engine_steps": engine.steps,
            "prefill_args_bytes": (mem.argument_size_in_bytes
                                   if mem is not None else "n/a"),
            "prefill_temp_bytes": (mem.temp_size_in_bytes
                                   if mem is not None else "n/a"),
            "device_bytes_limit": limit or "n/a",
            "solo_identical": True, "logits_rel_l2": f"{rel:.3e}",
            "logits_tol": LOGITS_REL_L2}


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------
def mesh_training(arch: str, *, smoke: bool, n_layers, devices,
                  steps: int, batch: int, seq: int, seed: int) -> dict:
    """Schedule a 4-GPU job, train it on a mesh built from its placement
    over ``devices``, and compare with the same steps on one device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_arch, make_inputs
    from repro.core import ClusterState, Job, JobKind, RSCH
    from repro.core.snapshot import FullSnapshotter
    from repro.core.topology import small_topology
    from repro.launch.cosched import job_mesh_shape
    from repro.launch.mesh import make_mesh
    from repro.models import Model
    from repro.sharding.auto import ShardingRules, param_shardings
    from repro.train import AdamWConfig, adamw_init, make_train_step

    topo = small_topology(n_nodes=4, gpus_per_node=len(devices))
    state = ClusterState.create(topo)
    job = Job(uid=1, tenant="t0", gpu_type=0, n_pods=1,
              gpus_per_pod=len(devices), kind=JobKind.TRAIN)
    res = RSCH(topo).schedule(job, FullSnapshotter().take(state))
    if res.placement is None:
        fail(f"{len(devices)}-GPU job not placed: {res.reason}")
    shape = job_mesh_shape(res.placement.n_gpus)

    cfg = get_arch(arch, smoke=smoke)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    opt_cfg = AdamWConfig(lr=1e-4)
    data = make_inputs(cfg, batch=batch, seq=seq, kind="train", seed=seed)

    def run(mesh):
        rules = ShardingRules(mesh)
        init = jax.jit(Model(cfg).init)
        specs = jax.eval_shape(init, jax.random.PRNGKey(seed))
        p_sh = param_shardings(specs, rules)
        params = jax.jit(init, out_shardings=p_sh)(
            jax.random.PRNGKey(seed))
        rep = NamedSharding(mesh, P())
        opt = jax.jit(adamw_init, out_shardings={
            "m": p_sh, "v": p_sh, "step": rep})(params)
        b = jax.device_put(data, rep)
        step = jax.jit(make_train_step(cfg, opt_cfg, remat=True))
        out = []
        for _ in range(steps):
            params, opt, m = step(params, opt, b)
            out.append((float(m["loss"]), float(m["grad_norm"])))
        specs_used = sorted({str(s.spec) for s in jax.tree.leaves(p_sh)})
        return out, specs_used

    with jax.default_matmul_precision("highest"):
        sharded, specs_used = run(make_mesh(shape, ("data", "model"),
                                            devices=devices))
        single, _ = run(make_mesh((1, 1), ("data", "model"),
                                  devices=devices[:1]))
    for i, ((l4, g4), (l1, g1)) in enumerate(zip(sharded, single)):
        if not (np.isfinite([l4, g4]).all()
                and abs(l4 - l1) <= TRAIN_LOSS_RTOL * abs(l1)
                and abs(g4 - g1) <= TRAIN_GNORM_RTOL * abs(g1)):
            fail(f"step {i}: mesh loss/gnorm {l4:.6f}/{g4:.6f} vs one "
                 f"device {l1:.6f}/{g1:.6f}")
    return {"arch": cfg.name, "layers": cfg.n_layers,
            "d_model": cfg.d_model, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "vocab": cfg.vocab,
            "mesh": f"{shape[0]}x{shape[1]}", "batch": batch, "seq": seq,
            "loss_mesh": [f"{l:.6f}" for l, _ in sharded],
            "loss_one": [f"{l:.6f}" for l, _ in single],
            "gnorm_mesh": [f"{g:.5f}" for _, g in sharded],
            "gnorm_one": [f"{g:.5f}" for _, g in single],
            "param_specs": "|".join(specs_used).replace(" ", ""),
            "tol": f"loss{TRAIN_LOSS_RTOL}/gnorm{TRAIN_GNORM_RTOL}"}


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh-training path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(f"[smoke] smoke run, not a measurement: device={dev.device_kind}"
          f" count={len(devices)} cache={cache_dir}", flush=True)

    if args.four_chips:
        ph = Phase("mesh-train", counter, dev)
        out = mesh_training(TRAIN_ARCH, smoke=False, n_layers=TRAIN_LAYERS,
                            devices=devices[:4], steps=TRAIN_STEPS,
                            batch=8, seq=512, seed=args.seed)
        ph.done(**out)
    else:
        ph = Phase("score-kernel", counter, dev)
        calls = kernel_lowering()
        if calls < 1:
            fail("pallas score call holds no tpu_custom_call")
        ph.done(nodes=SCHED_NODES, tpu_custom_calls=calls)

        ph = Phase("kant-cluster", counter, dev)
        out = trace_identity(SCHED_NODES, SCHED_JOBS, args.seed, counter)
        ph.done(nodes=SCHED_NODES, gpus=SCHED_NODES * 8,
                full_table_scored="yes(subset scoring is np-only)",
                placements="identical", **out)

        ph = Phase("1M-cycle", counter, dev)
        out = big_cycle(BIG_NODES)
        ph.done(placements="identical", **out)

        ph = Phase("serve", counter, dev)
        out = serving(SERVE_ARCH, smoke=False, n_layers=SERVE_LAYERS,
                      requests=SERVE_REQUESTS, seed=args.seed)
        ph.done(**out)
    c, s, h = counter.snapshot()
    print(f"[smoke] total: compiles={c} compile_s={s:.3f} "
          f"cache_hits={h}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
