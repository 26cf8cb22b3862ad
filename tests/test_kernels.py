"""Node-score kernel: numpy == jnp oracle == Pallas(interpret) across a
hypothesis sweep of shapes/dtypes/weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install .[test] for the "
                    "property-based kernel sweep")
from hypothesis import given, settings, strategies as st

from repro.core.scoring import (BINPACK, E_BINPACK, E_SPREAD, NEG_INF,
                                SPREAD, ScoreWeights, node_scores_np)
from repro.kernels.ops import best_node, node_scores


def _table(rng, n, g=8):
    free = rng.integers(0, g + 1, size=n).astype(np.int32)
    used = (g - free).astype(np.int32)
    mask = rng.random(n) < 0.8
    group_load = rng.random(n).astype(np.float32)
    topo_pref = rng.random(n).astype(np.float32)
    return free, used, mask, group_load, topo_pref


STRATEGIES = [BINPACK, E_BINPACK, SPREAD, E_SPREAD,
              ScoreWeights(used=0.3, fit=-0.2, group=1.1, topo=-0.7)]


@given(n=st.integers(1, 3000), seed=st.integers(0, 99),
       strat=st.sampled_from(STRATEGIES), request=st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_ref_matches_numpy(n, seed, strat, request):
    rng = np.random.default_rng(seed)
    free, used, mask, gl, tp = _table(rng, n)
    want = node_scores_np(free, used, mask, gl, tp, request, 8, strat)
    got = node_scores(free, used, mask, gl, tp, request=request,
                      gpus_per_node=8, weights=strat, backend="ref")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.parametrize("n", [1, 7, 128, 1000, 8192, 8193])
@pytest.mark.parametrize("strat", [E_BINPACK, E_SPREAD])
def test_pallas_interpret_matches_ref(n, strat):
    rng = np.random.default_rng(n)
    free, used, mask, gl, tp = _table(rng, n)
    ref = node_scores(free, used, mask, gl, tp, request=4,
                      gpus_per_node=8, weights=strat, backend="ref")
    pal = node_scores(free, used, mask, gl, tp, request=4,
                      gpus_per_node=8, weights=strat, backend="interpret")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6)


def test_padding_rows_never_win():
    """Padding must carry -inf so argmax cannot select a phantom node."""
    n = 130                                  # forces padding to 1024
    free = np.full(n, 8, np.int32)
    used = np.zeros(n, np.int32)
    mask = np.zeros(n, bool)
    mask[17] = True
    gl = np.zeros(n, np.float32)
    tp = np.zeros(n, np.float32)
    idx = best_node(free, used, mask, gl, tp, request=4, gpus_per_node=8,
                    weights=E_BINPACK, backend="interpret")
    assert idx == 17


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("n", [1, 130, 1000, 8193])
def test_scores_and_slots_fused_pass(backend, n):
    """Batched gang placement front half: the fused (scores, slots) pass
    agrees with the scalar score kernel + floor(free/request) expansion."""
    from repro.kernels.ops import node_scores_and_slots
    rng = np.random.default_rng(n)
    free, used, mask, gl, tp = _table(rng, n)
    scores, slots = node_scores_and_slots(
        free, used, mask, gl, tp, request=4, gpus_per_node=8,
        weights=E_BINPACK, backend=backend)
    want_scores = node_scores_np(free, used, mask, gl, tp, 4, 8, E_BINPACK)
    want_slots = np.where(want_scores > NEG_INF, free // 4, 0)
    np.testing.assert_allclose(np.asarray(scores), want_scores, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(slots), want_slots)


@pytest.mark.parametrize("counts", [True, False])
def test_scores_and_slots_phases_under_observer(counts):
    """With an observer attached the interpret backend enters its four
    phases once each, in order, counts the bytes each way (where the
    observer counts), and returns the same numpy arrays as untraced."""
    from conftest import PhaseLog
    from repro.kernels.ops import node_scores_and_slots
    n = 1000
    cols = _table(np.random.default_rng(5), n)
    kw = dict(request=4, gpus_per_node=8, weights=E_BINPACK,
              backend="interpret")
    log = PhaseLog(counts=counts)
    scores, slots = node_scores_and_slots(*cols, obs=log, **kw)
    parts = ["score-upload", "score-launch", "score-wait", "score-fetch"]
    assert log.events == [(e, p) for p in parts for e in ("enter", "exit")]
    assert isinstance(scores, np.ndarray) and isinstance(slots, np.ndarray)
    plain_scores, plain_slots = node_scores_and_slots(*cols, **kw)
    np.testing.assert_array_equal(scores, plain_scores)
    np.testing.assert_array_equal(slots, plain_slots)
    free, used, mask, gl, tp = cols
    want = node_scores_np(free, used, mask, gl, tp, 4, 8, E_BINPACK)
    np.testing.assert_allclose(scores, want, rtol=1e-6)
    np.testing.assert_array_equal(slots,
                                  np.where(want > NEG_INF, free // 4, 0))
    padded = 1024           # below one block: one (8, 128) tile's height
    # The five columns travel as an int32 and a float32 table: 4 bytes
    # each per padded node, the bool mask included.
    want_counts = {
        "score-h2d-bytes": padded * 5 * 4,
        "score-d2h-bytes": n * (4 + 4)} if counts else {}
    assert log.counted == want_counts


def _eager_chain(cols, n, kw):
    """The score call as it was before host staging: per column a jnp
    pad, concatenate and reshape to the kernel's tiling, the kernels,
    and a reshape and slice of each output, all eager."""
    from repro.kernels import node_score as ns
    padded = -(-n // (ns.LANE * ns.BLOCK_ROWS)) * ns.LANE * ns.BLOCK_ROWS
    tiles = []
    for c, fill in zip(cols, (0, 0, 0, 0.0, 0.0)):
        c = jnp.asarray(c)
        c = jnp.concatenate([c, jnp.full((padded - n,), fill, c.dtype)])
        tiles.append(c.reshape(-1, ns.LANE))
    scores, slots = ns.node_scores_slots_pallas(*tiles, interpret=True,
                                                **kw)
    alone = ns.node_scores_pallas(*tiles, interpret=True, **kw)
    return (np.asarray(scores.reshape(padded)[:n]),
            np.asarray(slots.reshape(padded)[:n]),
            np.asarray(alone.reshape(padded)[:n]))


def _typed_table(rng, n, counts, mask, loads):
    free, used, m, gl, tp = _table(rng, n)
    return (free.astype(counts), used.astype(counts), m.astype(mask),
            (gl + rng.random(n)).astype(loads),
            (tp + rng.random(n)).astype(loads))


_KW = dict(request=4, gpus_per_node=8, w_used=E_BINPACK.used,
           w_fit=E_BINPACK.fit, w_group=E_BINPACK.group,
           w_topo=E_BINPACK.topo)


@pytest.mark.parametrize("dtypes", [("int64", "bool", "float64"),
                                    ("int32", "int32", "float32"),
                                    ("int32", "bool", "float32")],
                         ids=["wide", "rsch", "table"])
@pytest.mark.parametrize("n", [1, 128, 130, 1000, 1024, 8191, 8192, 8193,
                               10000])
def test_staged_call_equals_eager_chain(n, dtypes):
    """Host staging and one program give the eager chain's scores and
    slots bit for bit, for every column dtype the callers hand in, and
    the jnp oracle's slots exactly and scores to float32 rounding.  A
    table below one block is staged at its own height in whole (8, 128)
    tiles; from one block up, in whole blocks."""
    from repro.kernels import node_score as ns
    from repro.kernels.ops import node_scores_and_slots, stage_tables
    from repro.kernels.ref import node_scores_slots_ref
    cols = _typed_table(np.random.default_rng(n), n, *dtypes)
    rows = stage_tables(cols, n)[0].shape[1]
    block = ns.LANE * ns.BLOCK_ROWS
    if n < block:
        assert rows % ns.SUBLANE == 0 and rows <= ns.BLOCK_ROWS
        assert rows == -(-n // (ns.LANE * ns.SUBLANE)) * ns.SUBLANE
    else:
        assert rows * ns.LANE == -(-n // block) * block
    want_scores, want_slots, want_alone = _eager_chain(cols, n, _KW)
    scores, slots = node_scores_and_slots(*cols, backend="interpret",
                                          **_KW)
    assert scores.dtype == np.float32 and slots.dtype == np.int32
    np.testing.assert_array_equal(scores, want_scores)
    np.testing.assert_array_equal(slots, want_slots)
    alone = node_scores(*cols, backend="interpret", **_KW)
    assert alone.shape == (n,)
    np.testing.assert_array_equal(np.asarray(alone), want_alone)
    # On the CPU, XLA fuses the oracle's multiply-adds differently from
    # the interpreted kernel's, so scores may differ in the last bit.
    ref_scores, ref_slots = node_scores_slots_ref(
        *(jnp.asarray(c) for c in cols), **_KW)
    np.testing.assert_array_equal(slots, np.asarray(ref_slots))
    np.testing.assert_allclose(scores, np.asarray(ref_scores), rtol=1e-6)


def test_successive_staged_calls_share_no_state():
    """A second call's staging leaves the first call's staged tables and
    results as they were, and each call scores its own columns."""
    from repro.kernels.ops import node_scores_and_slots, stage_tables
    n = 1000
    a = _typed_table(np.random.default_rng(1), n, "int64", "bool",
                     "float64")
    b = _typed_table(np.random.default_rng(2), n, "int64", "bool",
                     "float64")
    tables_a = stage_tables(a, n)
    kept = [t.copy() for t in tables_a]
    tables_b = stage_tables(b, n)
    # Below one block: 8 rows of 128 nodes.
    assert [t.shape for t in tables_a] == [(3, 8, 128), (2, 8, 128)]
    for ta, tb, k in zip(tables_a, tables_b, kept):
        np.testing.assert_array_equal(ta, k)
        assert not np.shares_memory(ta, tb)
    got_a = node_scores_and_slots(*a, backend="interpret", **_KW)
    got_b = node_scores_and_slots(*b, backend="interpret", **_KW)
    for got, cols in ((got_a, a), (got_b, b)):
        want = _eager_chain(cols, n, _KW)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert not np.array_equal(got_a[0], got_b[0])


@pytest.mark.parametrize("n", [1, 128, 130, 8193])
def test_score_call_fetches_one_buffer(n):
    """The score call's program returns one int32 device array, the
    scores' float32 bits over the pod slots, and the call returns its
    two rows as float32 scores and int32 slots, bit for bit those of the
    five-column kernel on the same columns: NEG_INF and 0 slots at
    masked nodes and at nodes with less free than the request."""
    from repro.kernels.ops import (node_scores_and_slots,
                                   scores_slots_program, stage_tables)
    cols = _table(np.random.default_rng(n), n)
    free, _, mask, _, _ = cols
    mask[0], free[-1] = False, _KW["request"] - 1
    packed = scores_slots_program(*jax.device_put(stage_tables(cols, n)),
                                  n=n, interpret=True, **_KW)
    assert isinstance(packed, jax.Array)
    assert packed.shape == (2, 1, n) and packed.dtype == jnp.int32
    scores, slots = node_scores_and_slots(*cols, backend="interpret",
                                          **_KW)
    assert scores.dtype == np.float32 and slots.dtype == np.int32
    assert scores.shape == slots.shape == (n,)
    np.testing.assert_array_equal(np.asarray(packed).reshape(2, n),
                                  np.stack([scores.view(np.int32), slots]))
    want_scores, want_slots, _ = _eager_chain(cols, n, _KW)
    np.testing.assert_array_equal(scores, want_scores)
    np.testing.assert_array_equal(slots, want_slots)
    invalid = ~mask | (free < _KW["request"])
    assert invalid[0] and invalid[-1]
    assert (scores[invalid] == NEG_INF).all() and not slots[invalid].any()


def test_no_valid_node_returns_minus_one():
    free = np.zeros(64, np.int32)
    used = np.full(64, 8, np.int32)
    mask = np.ones(64, bool)
    z = np.zeros(64, np.float32)
    idx = best_node(free, used, mask, z, z, request=1, gpus_per_node=8,
                    weights=BINPACK, backend="ref")
    assert idx == -1


def test_scheduler_scoring_agrees_with_kernel(topo, state):
    """RSCH's numpy scoring pass == the kernel on real cluster state."""
    from repro.core.snapshot import FullSnapshotter
    snap = FullSnapshotter().take(state)
    free = snap.free_gpus
    used = snap.used_gpus
    mask = snap.node_healthy
    gl = np.zeros(topo.n_nodes, np.float32)
    tp = np.zeros(topo.n_nodes, np.float32)
    want = node_scores_np(free, used, mask, gl, tp, 4, 8, E_BINPACK)
    got = node_scores(free, used, mask, gl, tp, request=4,
                      gpus_per_node=8, weights=E_BINPACK,
                      backend="interpret")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


# ---------------------------------------------------------------------------
# wkv6: RWKV-6 WKV recurrence kernel (kernels/wkv6.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,n,tb", [
    (1, 16, 1, 8, 8),
    (2, 32, 3, 8, 16),
    (2, 64, 2, 16, 64),     # tb == T: single time block
    (3, 48, 5, 4, 16),      # odd head count, tiny head dim
])
def test_wkv6_kernel_matches_ref(B, T, H, n, tb):
    from repro.kernels.ops import wkv6
    ks = jax.random.split(jax.random.PRNGKey(B * T + H), 6)
    r, k, v = (jax.random.normal(ki, (B, T, H, n)) * 0.5 for ki in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, n)))
    u = jax.random.normal(ks[4], (H, n)) * 0.5
    s0 = jax.random.normal(ks[5], (B, H, n, n)) * 0.1
    o_ref, sT_ref = wkv6(r, k, v, w, u, s0, backend="ref")
    o_pl, sT_pl = wkv6(r, k, v, w, u, s0, backend="interpret", tb=tb)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sT_pl), np.asarray(sT_ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_kernel_dtypes(dtype):
    from repro.kernels.ops import wkv6
    B, T, H, n = 2, 16, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    r, k, v = ((jax.random.normal(ki, (B, T, H, n)) * 0.5).astype(dtype)
               for ki in ks[:3])
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, n))).astype(dtype)
    u = (jax.random.normal(ks[4], (H, n)) * 0.5).astype(dtype)
    s0 = (jax.random.normal(ks[5], (B, H, n, n)) * 0.1).astype(jnp.float32)
    o_ref, sT_ref = wkv6(r, k, v, w, u, s0, backend="ref")
    o_pl, sT_pl = wkv6(r, k, v, w, u, s0, backend="interpret", tb=8)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=tol, rtol=tol)


def test_time_mix_kernel_backend_matches_scan():
    """rwkv6.time_mix(backend='interpret') == the step-scan layer path."""
    from repro.models import rwkv6 as rw
    d, hd, T, B = 32, 8, 24, 2
    p = rw.init_rwkv_block(jax.random.PRNGKey(0), d, 64, hd, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, d)) * 0.5
    st0 = jnp.zeros(rw.rwkv_state_shape(B, d, hd), jnp.float32)
    xl = jnp.zeros((B, d))
    o_scan, s_scan, _ = rw.time_mix(p, x, st0, xl, backend="scan")
    o_ker, s_ker, _ = rw.time_mix(p, x, st0, xl, backend="interpret")
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_scan),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(s_ker), np.asarray(s_scan),
                               atol=2e-5, rtol=2e-5)
