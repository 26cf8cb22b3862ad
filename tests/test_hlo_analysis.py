"""Trip-count-aware HLO analyzer: validated against hand-computable
programs (the roofline numbers are only as good as this parser)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import (HloModule, analyse_hlo_text,
                                       top_contributors)
from repro.launch.mesh import make_mesh


def _compile_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_scan_flops_scale_with_trip_count():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c

    d = 128
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    flops = {}
    for L in (4, 16):
        w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
        r = analyse_hlo_text(_compile_text(f, x, w))
        flops[L] = r["flops_per_device"]
        # dominated by L matmuls of 2*d^3
        assert abs(flops[L] - L * 2 * d**3) / (L * 2 * d**3) < 0.05
    assert 3.5 < flops[16] / flops[4] < 4.5


def test_single_matmul_flops_exact():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    r = analyse_hlo_text(_compile_text(f, a, b))
    assert r["flops_per_device"] >= 2 * 64 * 256 * 32
    assert r["flops_per_device"] < 2.2 * 64 * 256 * 32


def test_scan_bytes_do_not_count_full_stack_per_step():
    """The layer scan reads one (d,d) slice per step, not the (L,d,d)
    stack — the slice-aware fusion accounting must see that."""
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c

    d, L = 256, 32
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    r = analyse_hlo_text(_compile_text(f, x, w))
    stack_bytes = L * d * d * 4
    # roughly: per step read w slice + read/write c (+ tanh temp, dot
    # operands) ~ 8 slices; catastrophic would be L * stack_bytes (32x).
    assert r["bytes_per_device"] < 12 * stack_bytes
    assert r["bytes_per_device"] > stack_bytes          # every slice read


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.tanh(ci @ wi), None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        c, _ = jax.lax.scan(outer, x, w)
        return c

    d, L = 64, 5
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    w = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    r = analyse_hlo_text(_compile_text(f, x, w))
    want = L * 3 * 2 * d**3
    assert abs(r["flops_per_device"] - want) / want < 0.1


def test_top_contributors_orders_by_weight():
    def f(x, w, big):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        c, _ = jax.lax.scan(body, x, w)
        return c.sum() + (big @ big).sum()

    d = 64
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    w = jax.ShapeDtypeStruct((100, d, d), jnp.float32)   # 100 small dots
    big = jax.ShapeDtypeStruct((256, 256), jnp.float32)  # 1 big dot
    txt = _compile_text(f, x, w, big)
    rows = top_contributors(HloModule(txt), "flops", 5)
    # the loop-weighted small dot (100 * 2*64^3 = 5.2e7) must outrank the
    # single big dot (2*256^3 = 3.4e7)
    assert rows[0][0] > rows[1][0]
    assert rows[0][0] == pytest.approx(100 * 2 * d**3, rel=0.05)


def test_collective_parse_on_sharded_program():
    mesh = make_mesh((1,), ("x",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    def f(a):
        return a.sum()

    a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    with mesh:
        txt = jax.jit(f, in_shardings=NamedSharding(mesh, P("x", None))
                      ).lower(a).compile().as_text()
    r = analyse_hlo_text(txt)      # 1-device mesh: no collectives emitted
    assert r["collective_bytes_per_device"] >= 0.0


def test_scan_stacking_is_billed_per_slice_not_per_buffer():
    """A scan that stacks its per-step output writes one slice per trip
    in place (DUS-rooted fusion).  Billing the full (T, ...) history per
    step over-counts by ~T (the rwkv6 train_4k 5414s->18s correction,
    EXPERIMENTS.md §Perf iteration 0)."""
    def f(x, w):
        def body(c, wi):
            c = jnp.tanh(c @ wi)
            return c, c            # stacked ys output: (T, d, d)
        _, ys = jax.lax.scan(body, x, w)
        return ys

    d, T = 128, 64
    x = jax.ShapeDtypeStruct((d, d), jnp.float32)
    w = jax.ShapeDtypeStruct((T, d, d), jnp.float32)
    r = analyse_hlo_text(_compile_text(f, x, w))
    slice_bytes = d * d * 4
    # per step: weight-slice read (3 incl. fusion boundary), dot (3),
    # tanh (2), stacked in-place write (3) ~= 11 slices; the buggy
    # accounting billed the full T-slice stack per step (~T^2 total).
    per_step = r["bytes_per_device"] / T
    assert per_step < 13 * slice_bytes, (
        f"per-step bytes {per_step:.3e} suggests the full stack is "
        f"billed per step ({T * slice_bytes:.3e})")
    # sanity: at least the in-place write + one operand read per step
    assert per_step >= 2 * slice_bytes
