"""Auto-sharder rule table: determinism + divisibility fallbacks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.launch.mesh import make_cpu_mesh
from repro.models import Model
from repro.sharding.auto import (ShardingRules, batch_specs,
                                 cache_specs_sharding, param_shardings,
                                 partition_spec)


@pytest.fixture(scope="module")
def rules():
    # A (4, 2) CPU mesh stands in for (data, model); the rule table only
    # reads axis sizes, so divisibility semantics are identical.
    mesh = make_cpu_mesh(1, 1)
    return ShardingRules(mesh)


@pytest.fixture(scope="module")
def rules_16x16():
    from jax.sharding import AbstractMesh
    return ShardingRules(AbstractMesh((16, 16), ("data", "model")))


def test_mlp_rules(rules_16x16):
    r = rules_16x16
    assert partition_spec("layers/mlp/w_gate", (40, 4096, 13696), r) == \
        P(None, "data", "model")
    assert partition_spec("layers/mlp/w_down", (40, 13696, 4096), r) == \
        P(None, "model", "data")


def test_attention_rules_with_fallback(rules_16x16):
    r = rules_16x16
    # 32 q heads divide 16 -> TP on heads
    assert partition_spec("layers/attn/wq", (40, 4096, 32, 128), r) == \
        P(None, "data", "model", None)
    # 2 kv heads do NOT divide 16 -> replicate heads (no hd fallback)
    assert partition_spec("layers/attn/wk", (40, 4096, 2, 128), r) == \
        P(None, "data", None, None)
    assert partition_spec("layers/attn/wo", (40, 32, 128, 4096), r) == \
        P(None, "model", None, "data")


def test_moe_expert_parallel_and_fallback(rules_16x16):
    r = rules_16x16
    # llama4: 128 experts divide 16 -> EP
    assert partition_spec("layers/moe/w_gate", (48, 128, 5120, 8192),
                          r) == P(None, "model", "data", None)
    # mixtral: 8 experts don't -> TP on d_ff instead
    assert partition_spec("layers/moe/w_gate", (32, 8, 4096, 14336),
                          r) == P(None, None, "data", "model")


def test_embed_and_head(rules_16x16):
    r = rules_16x16
    assert partition_spec("embed", (151552, 4096), r) == \
        P("model", "data")
    assert partition_spec("lm_head", (4096, 151552), r) == \
        P("data", "model")
    # seamless vocab 256206 is not divisible by 16 -> only data on d
    assert partition_spec("embed", (256206, 1024), r) == P(None, "data")


def test_norms_replicated(rules_16x16):
    assert partition_spec("layers/norm1", (40, 4096), rules_16x16) == P()
    assert partition_spec("final_norm", (4096,), rules_16x16) == P()


def test_every_param_of_every_arch_gets_a_spec(rules_16x16):
    """Rule table is total + deterministic over the whole zoo."""
    for arch_id in ARCH_IDS:
        cfg = get_arch(arch_id)
        specs = Model(cfg).param_specs()
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        for keypath, leaf in flat:
            path = "/".join(str(getattr(k, "key", k)) for k in keypath)
            spec1 = partition_spec(path, leaf.shape, rules_16x16)
            spec2 = partition_spec(path, leaf.shape, rules_16x16)
            assert spec1 == spec2
            # every sharded dim divides
            for dim, part in enumerate(spec1):
                if part is None:
                    continue
                size = 16
                assert leaf.shape[dim] % size == 0, (arch_id, path)


def test_batch_specs_divisibility(rules_16x16):
    specs = batch_specs(
        {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
         "odd": jax.ShapeDtypeStruct((1, 7), jnp.int32)}, rules_16x16)
    assert specs["tokens"].spec == P(("data",), None)
    assert specs["odd"].spec == P(None, None)


def test_cache_sharding_head_vs_window_fallback(rules_16x16):
    r = rules_16x16
    cache = {
        "layers": {
            # 8 kv heads don't divide 16 -> window dim gets model
            "k": jax.ShapeDtypeStruct((88, 128, 32768, 8, 128),
                                      jnp.bfloat16),
            # 16 kv heads divide -> heads get model
            "v": jax.ShapeDtypeStruct((24, 128, 32768, 16, 64),
                                      jnp.bfloat16),
        },
        "t": jax.ShapeDtypeStruct((), jnp.int32),
    }
    out = cache_specs_sharding(cache, r)
    assert out["layers"]["k"].spec == P(None, ("data",), "model", None,
                                        None)
    assert out["layers"]["v"].spec == P(None, ("data",), None, "model",
                                        None)
    assert out["t"].spec == P()
