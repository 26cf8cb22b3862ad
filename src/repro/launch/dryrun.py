"""Multi-pod dry-run (deliverable e) + roofline term extraction (g).

MUST be the process entry point (``python -m repro.launch.dryrun``):
the first two lines below force 512 placeholder host devices BEFORE any
jax import, because jax locks the device count on first init.  Never set
this globally — smoke tests and benchmarks see the single real CPU.

For every (architecture × input shape × mesh) the dry-run:

1. builds ``ShapeDtypeStruct`` stand-ins for params / optimizer / batch /
   cache (zero allocation),
2. ``jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
   .compile()`` under the production mesh,
3. records ``compiled.memory_analysis()`` (proves the working set fits),
   ``compiled.cost_analysis()`` (FLOPs / bytes for the roofline), and the
   per-device collective bytes parsed from the partitioned HLO
   (all-gather / all-reduce / reduce-scatter / all-to-all /
   collective-permute operand sizes),
4. writes one JSON per combination under ``experiments/dryrun/``.

Roofline terms (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI):
``cost_analysis`` runs on the *partitioned per-device module*, so

    compute    = flops_per_device / peak_flops      (s)
    memory     = bytes_per_device / hbm_bw          (s)
    collective = coll_bytes_per_device / ici_bw     (s)

which equal the brief's ``global / (chips × per-chip)`` formulas.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, SHAPES, get_arch, input_specs
from ..configs.base import ArchConfig, InputShape
from ..models.model import Model
from ..serve.step import make_decode_step, make_prefill_step
from ..sharding.auto import (ShardingRules, batch_specs,
                             cache_specs_sharding, param_shardings)
from ..train.optim import opt_specs
from ..train.step import make_train_step
from .combo_cache import ComboCache, mesh_key
from .mesh import V5E, chip_peaks, make_production_mesh

# Memoization for sweeps that revisit (arch × shape × mesh) combos —
# e.g. elastic-plan estimation probing one architecture at several chip
# counts.  Custom ``rules`` objects bypass the cache (their sharding is
# not captured by the key).  ``cache_stats()`` feeds the benchmarks'
# hit counters.
_LOWER_CACHE = ComboCache("dryrun-lower")
_ANALYSE_CACHE = ComboCache("dryrun-analyse")
# id(lowered) -> combo key, so analyse() can reuse the lowering's key
# without re-deriving it from jax objects.
_LOWERED_KEY: Dict[int, tuple] = {}


def _combo_key(cfg: ArchConfig, shape: InputShape, mesh, *, remat: bool,
               microbatches: int, seq_shard: bool,
               bf16_moments: bool) -> tuple:
    return (cfg.name, shape.name, mesh_key(mesh), bool(remat),
            int(microbatches), bool(seq_shard), bool(bf16_moments))


def cache_stats() -> Dict[str, Dict[str, Any]]:
    """Hit/miss/size counters of the lowering + analysis memo caches."""
    return {c.name: c.stats() for c in (_LOWER_CACHE, _ANALYSE_CACHE)}


def clear_caches() -> None:
    _LOWER_CACHE.clear()
    _ANALYSE_CACHE.clear()
    _LOWERED_KEY.clear()

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "all-to-all", "collective-permute")


# ---------------------------------------------------------------------------
# HLO parsing
# ---------------------------------------------------------------------------
def _shape_bytes(type_str: str) -> int:
    """'bf16[2,8]' -> 32.  Tuples handled by the caller."""
    m = re.match(r"(\w+)\[([\d,]*)\]", type_str)
    if not m:
        return 0
    dtype, dims = m.group(1), m.group(2)
    size = _DTYPE_BYTES.get(dtype, 4)
    if dims:
        for d in dims.split(","):
            size *= int(d)
    return size


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device operand bytes of every collective, by collective kind."""
    # name -> output bytes for every instruction
    sizes: Dict[str, int] = {}
    for m in re.finditer(
            r"%?([\w.\-]+) = \(?((?:\w+\[[\d,]*\][^)=]*?)+)\)? ", hlo_text):
        name, types = m.group(1), m.group(2)
        total = sum(_shape_bytes(t) for t in
                    re.findall(r"\w+\[[\d,]*\]", types))
        sizes[name] = total
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"%?[\w.\-]+ = .*? (" + "|".join(_COLLECTIVES)
                     + r")(?:-start|-done)?\(([^)]*)\)", stripped)
        if not m:
            continue
        kind, args = m.group(1), m.group(2)
        if "-done(" in stripped:
            continue                   # counted at the -start op
        for arg in args.split(", "):
            arg = arg.strip().lstrip("%")
            if arg in sizes:
                out[kind] += sizes[arg]
            else:
                # operand annotated inline: 'bf16[4,8]{1,0} %x'
                mm = re.match(r"(\w+\[[\d,]*\])", arg)
                if mm:
                    out[kind] += _shape_bytes(mm.group(1))
    return out


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------
def lower_combo(cfg: ArchConfig, shape: InputShape, mesh, *,
                rules: Optional[ShardingRules] = None,
                remat: bool = True, microbatches: int = 1,
                seq_shard: bool = False, bf16_moments: bool = False):
    """Build the jitted step for one (arch × shape) and lower it.

    Memoized on (arch, shape, mesh axes, remat, microbatches,
    seq_shard, bf16_moments) unless explicit ``rules`` are passed."""
    from ..sharding.context import use_activation_sharding
    key = None
    if rules is None:
        key = _combo_key(cfg, shape, mesh, remat=remat,
                         microbatches=microbatches, seq_shard=seq_shard,
                         bf16_moments=bf16_moments)
        cached = _LOWER_CACHE.get(key)
        if cached is not None:
            return cached
    rules = rules or ShardingRules(mesh)
    model = Model(cfg)
    p_specs = model.param_specs(jnp.bfloat16)
    p_shard = param_shardings(p_specs, rules)
    b_specs = input_specs(cfg, shape)
    b_shard = batch_specs(b_specs, rules)

    with mesh, use_activation_sharding(mesh, seq_shard=seq_shard):
        if shape.kind == "train":
            from jax.sharding import NamedSharding, PartitionSpec as P
            o_specs = opt_specs(p_specs,
                                moment_dtype=jnp.bfloat16 if bf16_moments
                                else jnp.float32)
            o_shard = {"m": p_shard, "v": p_shard,
                       "step": NamedSharding(mesh, P())}
            step = make_train_step(cfg, remat=remat,
                                   microbatches=microbatches,
                                   grad_shardings=p_shard)
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(p_specs, o_specs, b_specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, shape.seq_len)
            c_specs = model.cache_specs(shape.global_batch, shape.seq_len,
                                        jnp.bfloat16)
            c_shard = cache_specs_sharding(c_specs, rules)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard),
                             out_shardings=(None, c_shard))
            lowered = jitted.lower(p_specs, b_specs)
        else:                                  # decode
            step = make_decode_step(cfg)
            c_specs = model.cache_specs(shape.global_batch, shape.seq_len,
                                        jnp.bfloat16)
            c_shard = cache_specs_sharding(c_specs, rules)
            t_shard = batch_specs(
                {"token": b_specs["token"]}, rules)["token"]
            jitted = jax.jit(step,
                             in_shardings=(p_shard, c_shard, t_shard),
                             out_shardings=(None, c_shard),
                             donate_argnums=(1,))
            lowered = jitted.lower(p_specs, c_specs, b_specs["token"])
    if key is not None:
        _LOWER_CACHE.put(key, lowered)
        _LOWERED_KEY[id(lowered)] = key
    return lowered


def model_flops(cfg: ArchConfig, shape: InputShape) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (fwd-only), N active for MoE."""
    model = Model(cfg)
    n = model.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # one token per sequence


def analyse(lowered, cfg: ArchConfig, shape: InputShape, n_chips: int
            ) -> Dict[str, Any]:
    from .hlo_analysis import analyse_hlo_text
    # Memoized when the lowering came out of lower_combo's cache path:
    # compile + HLO reanalysis dominate a sweep's wall time.  Callers
    # get a fresh dict (run_one mutates its result).
    memo_key = None
    lkey = _LOWERED_KEY.get(id(lowered))
    if lkey is not None:
        memo_key = (lkey, int(n_chips))
        cached = _ANALYSE_CACHE.get(memo_key)
        if cached is not None:
            return dict(cached)
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            mem_info[attr] = int(getattr(mem, attr))
    # Trip-count-aware reanalysis of the partitioned HLO (cost_analysis
    # counts while bodies once — see hlo_analysis module docstring).
    hlo = analyse_hlo_text(compiled.as_text())
    flops_dev = float(hlo["flops_per_device"])
    bytes_dev = float(hlo["bytes_per_device"])
    coll = {k: float(v) for k, v in hlo["collectives"].items()}
    coll_total = float(hlo["collective_bytes_per_device"])

    mf = model_flops(cfg, shape)
    peaks = chip_peaks(V5E)
    flops_global = flops_dev * n_chips
    result = {
        "arch": cfg.name, "shape": shape.name, "chips": n_chips,
        "compile_s": round(compile_s, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes},
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "memory_analysis": mem_info,
        "model_flops_global": mf,
        "useful_flops_ratio": (mf / flops_global) if flops_global else 0.0,
        "compute_term_s": flops_dev / peaks.flops_bf16,
        "memory_term_s": bytes_dev / peaks.hbm_bw,
        "collective_term_s": coll_total / peaks.ici_bw,
    }
    terms = {"compute": result["compute_term_s"],
             "memory": result["memory_term_s"],
             "collective": result["collective_term_s"]}
    result["dominant_term"] = max(terms, key=terms.get)
    if memo_key is not None:
        _ANALYSE_CACHE.put(memo_key, dict(result))
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def run_one(arch_id: str, shape_name: str, multi_pod: bool,
            out_dir: str, *, remat: bool = True,
            rules_name: str = "baseline", microbatches: int = 1,
            seq_shard: bool = False,
            bf16_moments: bool = False) -> Dict[str, Any]:
    cfg = get_arch(arch_id)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    lowered = lower_combo(cfg, shape, mesh, remat=remat,
                          microbatches=microbatches, seq_shard=seq_shard,
                          bf16_moments=bf16_moments)
    lower_s = time.time() - t0
    result = analyse(lowered, cfg, shape, n_chips)
    result["lower_s"] = round(lower_s, 2)
    result["mesh"] = "2x16x16" if multi_pod else "16x16"
    result["rules"] = rules_name
    result["microbatches"] = microbatches
    result["seq_shard"] = seq_shard
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch_id}__{shape_name}__{result['mesh']}__{rules_name}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="input shape name or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--rules", default="baseline",
                    help="tag recorded in the artifact filename")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches (train)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel layer-boundary activations")
    ap.add_argument("--bf16-moments", action="store_true",
                    help="store AdamW moments in bf16 (halves opt HBM)")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for a in archs:
        for s in shapes:
            tag = f"{a} × {s} × {'2x16x16' if args.multi_pod else '16x16'}"
            try:
                r = run_one(a, s, args.multi_pod, args.out,
                            remat=not args.no_remat,
                            rules_name=args.rules,
                            microbatches=args.microbatches,
                            seq_shard=args.seq_shard,
                            bf16_moments=args.bf16_moments)
                print(f"[ok] {tag}: dominant={r['dominant_term']} "
                      f"compute={r['compute_term_s']:.3e}s "
                      f"memory={r['memory_term_s']:.3e}s "
                      f"collective={r['collective_term_s']:.3e}s "
                      f"(compile {r['compile_s']}s)", flush=True)
            except Exception as e:   # noqa: BLE001 — report, keep going
                failures.append(tag)
                print(f"[FAIL] {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"{len(failures)} failures: {failures}")
        return 1
    print("all dry-runs passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
