"""Pallas TPU kernel: RWKV-6 (Finch) WKV recurrence with VMEM-resident
state.

The pure-jnp formulation (``rwkv6.time_mix``) scans one token at a time
and the (B, H, n, n) f32 state round-trips HBM on *every step* — ~3 state
reads/writes x 4096 steps x 32 layers dominates the rwkv6-3b x train_4k
memory roofline term (14+ s of 18 s; EXPERIMENTS.md §Perf).  On TPU the
fix is structural: keep the per-(batch, head) ``(n, n)`` state in VMEM
for the whole sequence and stream only the r/k/v/w inputs and the o
output through HBM.

Layout / grid:

* inputs r, k, v, w: ``(B, T, H, n)`` — the natural stream layout,
  which the wrapper transposes to ``(B, H, T, n)`` so that each stream
  block ``(1, 1, TB, n)`` is tiled as the TPU requires;
* grid ``(B, H, T // TB)`` with ``dimension_semantics``
  ``("parallel", "parallel", "arbitrary")`` — time is the sequential
  grid axis, so the ``(n, n)`` state lives in a VMEM scratch buffer that
  persists across the time blocks of one (b, h);
* per step (inside a block): ``o_t = r_t @ S + (r_t·u·k_t) v_t`` and
  ``S <- w_t[:, None] * S + k_t^T v_t`` — the ``u``-bonus needs no
  materialized ``kv`` outer product on the output path;
* the final state is written once per (b, h) when the last time block
  retires.

Per-(b, h) VMEM footprint: 4 stream blocks (TB, n) + state (n, n) + out
(TB, n) — ~0.4 MB at TB=256, n=64, far under the v5e VMEM budget, so the
compiler can double-buffer the streams.

HBM bytes collapse from O(T·n²) state traffic to O(T·n) streams — the
§Perf log records the analytic roofline (the CPU dry-run cannot observe
VMEM residency, so this win is reported analytically, validated by the
interpret-mode allclose tests in tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TB = 256


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sT_ref, state, *, tb: int, n_tblocks: int) -> None:
    """One (b, h, time-block) grid step.

    r/k/v/w_ref, o_ref: (1, 1, TB, n) VMEM blocks; u_ref: (1, 1, n);
    s0_ref, sT_ref: (1, 1, n, n); state: (n, n) f32 VMEM scratch.
    """
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                     # (1, n)

    def step(t, carry):
        # One time step as (1, n) rows: the TPU matmul takes 2-D operands.
        row = pl.ds(t, 1)
        r_t = r_ref[0, 0, row, :].astype(jnp.float32)    # (1, n)
        k_t = k_ref[0, 0, row, :].astype(jnp.float32)
        v_t = v_ref[0, 0, row, :].astype(jnp.float32)
        w_t = w_ref[0, 0, row, :].astype(jnp.float32)
        S = state[...]                                   # (n, n)
        # o_t[m] = sum_n r[n] (S[n,m] + u[n] k[n] v[m])
        o_t = (jnp.dot(r_t, S, preferred_element_type=jnp.float32)
               + jnp.sum(r_t * u * k_t) * v_t)
        o_ref[0, 0, row, :] = o_t.astype(o_ref.dtype)
        state[...] = w_t.T * S + k_t.T * v_t
        return carry

    jax.lax.fori_loop(0, tb, step, 0)

    @pl.when(tc == n_tblocks - 1)
    def _emit():
        sT_ref[0, 0] = state[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tb", "interpret"))
def wkv6_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                w: jnp.ndarray, u: jnp.ndarray, s0: jnp.ndarray,
                *, tb: int = DEFAULT_TB, interpret: bool = False):
    """RWKV-6 WKV over a full sequence.

    r, k, v, w: (B, T, H, n); u: (H, n); s0: (B, H, n, n).
    Returns (o (B, T, H, n) f32, sT (B, H, n, n) f32).
    """
    B, T, H, n = r.shape
    tb = min(tb, T)
    if T % tb:
        raise ValueError(f"T={T} not divisible by time block {tb}")
    n_tblocks = T // tb

    # The kernel streams (B, H, T, n): a (1, 1, tb, n) block's last two
    # dims are a multiple of 8 and the full head dim, which the TPU
    # lowering requires of a block (a (1, tb, 1, n) block of the natural
    # (B, T, H, n) layout is refused wherever H > 1).
    stream = pl.BlockSpec((1, 1, tb, n), lambda b, h, t: (b, h, t, 0))
    state_spec = pl.BlockSpec((1, 1, n, n), lambda b, h, t: (b, h, 0, 0))
    u_spec = pl.BlockSpec((1, 1, n), lambda b, h, t: (h, 0, 0))
    kernel = functools.partial(_wkv_kernel, tb=tb, n_tblocks=n_tblocks)

    out_shapes = (
        jax.ShapeDtypeStruct((B, H, T, n), jnp.float32),
        jax.ShapeDtypeStruct((B, H, n, n), jnp.float32),
    )
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    o, sT = pl.pallas_call(
        kernel,
        grid=(B, H, n_tblocks),
        in_specs=[stream, stream, stream, stream, u_spec, state_spec],
        out_specs=(stream, state_spec),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(*(x.transpose(0, 2, 1, 3) for x in (r, k, v, w)),
      u.reshape(H, 1, n), s0)
    return o.transpose(0, 2, 1, 3), sT
