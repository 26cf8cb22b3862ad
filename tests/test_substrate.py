"""Train/serve/data/ckpt substrate: loss decreases, optimizer, engine,
checkpoint roundtrip."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import load_checkpoint, save_checkpoint
from repro.configs import get_arch
from repro.data import DataConfig, synthetic_batches
from repro.models import Model
from repro.serve import Request, ServeEngine
from repro.train import (AdamWConfig, TrainState, adamw_init,
                         adamw_update, cross_entropy_loss)


def test_cross_entropy_basics():
    logits = jnp.zeros((1, 2, 4))
    labels = jnp.array([[1, 2]])
    loss = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(4.0), rtol=1e-6)
    # ignore_id masks positions
    labels = jnp.array([[1, -1]])
    loss = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(4.0), rtol=1e-6)


def test_adamw_moves_toward_minimum():
    params = {"w": jnp.asarray(5.0)}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    for _ in range(200):
        grads = {"w": 2.0 * params["w"]}        # d/dw w^2
        params, opt, _ = adamw_update(cfg, grads, opt, params)
    assert abs(float(params["w"])) < 0.1
    assert int(opt["step"]) == 200


def test_train_loss_decreases():
    """End-to-end: a tiny model learns the sticky-bigram structure."""
    cfg = get_arch("glm4-9b", smoke=True)
    state = TrainState(cfg, jax.random.PRNGKey(0),
                       AdamWConfig(lr=3e-3, weight_decay=0.0))
    data = synthetic_batches(cfg, DataConfig(batch=8, seq=32, seed=0))
    losses = [state.step(next(data))["loss"] for _ in range(30)]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses


def test_grad_clip_bounds_update():
    params = {"w": jnp.asarray(1.0)}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, grad_clip=1.0, weight_decay=0.0)
    _, _, gnorm = adamw_update(cfg, {"w": jnp.asarray(1e6)}, opt, params)
    assert float(gnorm) == 1e6          # reported raw


def test_serve_engine_drains_requests():
    cfg = get_arch("glm4-9b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_size=2, max_seq=64)
    rng = np.random.default_rng(0)
    for i in range(5):
        engine.submit(Request(uid=i,
                              prompt=rng.integers(0, cfg.vocab, size=6)
                              .astype(np.int32),
                              max_new_tokens=4))
    finished = engine.run_until_drained()
    assert len(finished) == 5
    assert all(len(r.generated) == 4 for r in finished)


def test_ckpt_roundtrip(tmp_path):
    cfg = get_arch("mixtral-8x7b", smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    opt = adamw_init(params)
    save_checkpoint(str(tmp_path), {"params": params, "opt": opt}, step=7)
    loaded = load_checkpoint(str(tmp_path))
    assert loaded["step"] == 7
    flat_a = jax.tree.leaves(params)
    flat_b = jax.tree.leaves(loaded["params"])
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_data_pipeline_is_learnable_structure():
    cfg = get_arch("glm4-9b", smoke=True)
    data = synthetic_batches(cfg, DataConfig(batch=4, seq=64, seed=0,
                                             stickiness=1.0))
    b = next(data)
    toks = np.asarray(b["tokens"])
    labs = np.asarray(b["labels"])
    # with stickiness 1.0 every label is the deterministic successor
    assert b["tokens"].shape == (4, 64)
    assert (labs[:, :-1] == toks[:, 1:]).all()


def test_microbatched_step_matches_single_shot():
    """Gradient-accumulation microbatching is numerically the full-batch
    step (same loss, same params after update)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch, make_inputs
    from repro.models.model import Model
    from repro.train.optim import adamw_init
    from repro.train.step import make_train_step

    cfg = get_arch("glm4-9b", smoke=True)
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    opt = adamw_init(p)
    b = make_inputs(cfg, batch=8, seq=16, kind="train")
    s1 = jax.jit(make_train_step(cfg, remat=False, microbatches=1))
    s4 = jax.jit(make_train_step(cfg, remat=False, microbatches=4))
    p1, _, m1 = s1(p, opt, b)
    p4, _, m4 = s4(p, opt, b)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
    d = max(float(jnp.max(jnp.abs(a - c))) for a, c in
            zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert d < 5e-5          # f32 accumulation-order noise only


def test_microbatches_must_divide_batch():
    import jax
    import pytest as _pytest
    from repro.configs import get_arch, make_inputs
    from repro.models.model import Model
    from repro.train.optim import adamw_init
    from repro.train.step import make_train_step

    cfg = get_arch("glm4-9b", smoke=True)
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    b = make_inputs(cfg, batch=6, seq=8, kind="train")
    step = make_train_step(cfg, remat=False, microbatches=4)
    with _pytest.raises(ValueError, match="not divisible"):
        step(p, adamw_init(p), b)


def test_bf16_moments_update_preserves_dtype_and_learns():
    import jax
    import jax.numpy as jnp
    from repro.train.optim import AdamWConfig, adamw_update

    p = {"w": jnp.ones((4, 4), jnp.float32)}
    opt = {"m": {"w": jnp.zeros((4, 4), jnp.bfloat16)},
           "v": {"w": jnp.zeros((4, 4), jnp.bfloat16)},
           "step": jnp.zeros((), jnp.int32)}
    g = {"w": jnp.full((4, 4), 0.5, jnp.float32)}
    cfg = AdamWConfig(lr=1e-2, weight_decay=0.0)
    new_p, new_opt, gn = adamw_update(cfg, g, opt, p)
    assert new_opt["m"]["w"].dtype == jnp.bfloat16
    assert new_opt["v"]["w"].dtype == jnp.bfloat16
    assert float(new_p["w"][0, 0]) < 1.0          # moved against the grad


def test_seq_shard_context_resolves_only_when_enabled():
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from repro.launch.mesh import make_mesh
    from repro.sharding.context import ActivationSharding

    mesh = make_mesh((1,), ("model",))
    off = ActivationSharding(mesh, seq_shard=False)
    on = ActivationSharding(mesh, seq_shard=True)
    assert off.resolve(4096, "seq") is None
    assert on.resolve(4096, "seq") == ("model",)
    assert on.resolve(4095, "seq") == ("model",)   # 1-way axis divides all
