"""Serving driver: batched requests through the ServeEngine.

``python -m repro.launch.serve --arch glm4-9b --requests 12`` serves the
reduced ("smoke") config with continuous batching and reports
per-request latency in engine steps.  ``--full`` serves the published
widths.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from ..configs import get_arch
from ..models.model import Model
from ..serve import Request, ServeEngine
from .compile_cache import enable_compile_cache


def serve_demo(arch: str, *, smoke: bool = True,
               n_layers: Optional[int] = None, requests: int = 12,
               batch_size: int = 4, max_new: int = 8, seed: int = 0,
               per_slot: bool = True):
    """Serve ``requests`` random prompts of 4-16 tokens; returns the
    finished requests and the engine (which holds the weights)."""
    cfg = get_arch(arch, smoke=smoke)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    engine = ServeEngine(cfg, params, batch_size=batch_size, max_seq=128,
                         per_slot_prefill=per_slot)
    rng = np.random.default_rng(seed)
    for i in range(requests):
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(4, 17)
                              ).astype(np.int32)
        engine.submit(Request(uid=i, prompt=prompt, max_new_tokens=max_new))
    t0 = time.time()
    finished = engine.run_until_drained()
    dt = time.time() - t0
    tokens = sum(len(r.generated) for r in finished)
    print(f"served {len(finished)}/{requests} requests, {tokens} tokens "
          f"in {engine.steps} engine steps ({dt:.1f}s, "
          f"{tokens / max(dt, 1e-9):.1f} tok/s)")
    return finished, engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--legacy", action="store_true",
                    help="use the legacy whole-batch re-prefill shim "
                         "instead of per-slot continuous batching")
    args = ap.parse_args()
    enable_compile_cache()
    serve_demo(args.arch, smoke=args.smoke, requests=args.requests,
               batch_size=args.batch_size, max_new=args.max_new,
               per_slot=not args.legacy)


if __name__ == "__main__":
    main()
