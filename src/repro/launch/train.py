"""CPU-scale training driver (examples / integration tests).

``python -m repro.launch.train --arch glm4-9b --smoke --steps 20`` runs a
reduced-config model end-to-end: synthetic data pipeline -> train_step ->
checkpoint.  On real hardware the same code path runs under the
production mesh with the auto-sharder (see dryrun.py for the lowering).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..ckpt import save_checkpoint
from ..configs import get_arch
from ..data import DataConfig, synthetic_batches
from ..train import AdamWConfig, TrainState
from .compile_cache import enable_compile_cache


def train_loop(arch: str, *, smoke: bool = True, steps: int = 20,
               batch: int = 8, seq: int = 64, lr: float = 1e-3,
               ckpt_dir: str = "", seed: int = 0, log_every: int = 5):
    cfg = get_arch(arch, smoke=smoke)
    state = TrainState(cfg, jax.random.PRNGKey(seed),
                       AdamWConfig(lr=lr, weight_decay=0.0))
    data = synthetic_batches(cfg, DataConfig(batch=batch, seq=seq,
                                             seed=seed))
    t0 = time.time()
    for i in range(steps):
        metrics = state.step(next(data))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d}  loss {metrics['loss']:.4f}  "
                  f"gnorm {metrics['grad_norm']:.3f}  "
                  f"({time.time() - t0:.1f}s)", flush=True)
    if ckpt_dir:
        save_checkpoint(ckpt_dir, {"params": state.params,
                                   "opt": state.opt_state}, step=steps)
        print(f"checkpoint written to {ckpt_dir}")
    return state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    enable_compile_cache()
    train_loop(args.arch, smoke=args.smoke, steps=args.steps,
               batch=args.batch, seq=args.seq, lr=args.lr,
               ckpt_dir=args.ckpt)


if __name__ == "__main__":
    main()
