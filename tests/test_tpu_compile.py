"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip.  They catch what
interpret mode cannot: block shapes the TPU lowering refuses, and
kernels that use more VMEM than a core has.  Nothing runs; a compile
that passes here is not a run on the chip.
"""

import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.scoring import E_BINPACK
from repro.kernels import node_score as ns, ops
from repro.kernels.wkv6 import wkv6_pallas

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench import readers, trace  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _node_table(n_nodes, sharding):
    """The five node columns as the ops wrapper pads them: (rows, LANE)."""
    rows = ops.padded_nodes(n_nodes) // ns.LANE
    cols = (jnp.int32, jnp.int32, jnp.int32, jnp.float32, jnp.float32)
    return [jax.ShapeDtypeStruct((rows, ns.LANE), dt, sharding=sharding)
            for dt in cols]


def _weights():
    w = E_BINPACK
    return dict(request=8, gpus_per_node=8, w_used=w.used, w_fit=w.fit,
                w_group=w.group, w_topo=w.topo)


@pytest.mark.parametrize("kernel", [ns.node_scores_pallas,
                                    ns.node_scores_slots_pallas],
                         ids=["scores", "scores_slots"])
@pytest.mark.parametrize("n_nodes", [128, 10_000, 1_000_000])
def test_node_score_kernels_compile(one_chip, kernel, n_nodes):
    compiled = kernel.lower(*_node_table(n_nodes, one_chip),
                            **_weights()).compile()
    assert "tpu_custom_call" in compiled.as_text()


# Instructions that run no device op of their own.
_NO_OP = ("parameter", "get-tuple-element", "bitcast", "tuple")


@pytest.mark.parametrize("program,key", [
    (ops.scores_program, "node_scores_pallas"),
    (ops.scores_slots_program, "node_scores_slots_pallas")],
    ids=["scores", "scores_slots"])
@pytest.mark.parametrize("n_nodes", [128, 10_000, 1_000_000])
def test_score_call_is_one_program_with_one_named_kernel(
        one_chip, program, key, n_nodes):
    """The score call's program, on the tables it is staged into, holds
    one Mosaic kernel named after its kernel function, reading both
    tables where they were handed in, and no other instruction that
    runs on the device names that kernel or copies a table before it:
    a trace finds the kernel's time by that name, and the kernel's time
    must hold its reads of the table."""
    rows = ops.padded_nodes(n_nodes) // ns.LANE
    tables = [jax.ShapeDtypeStruct((k, rows, ns.LANE), dt, sharding=one_chip)
              for k, dt in ((3, jnp.int32), (2, jnp.float32))]
    text = program.lower(*tables, n=n_nodes,
                         **_weights()).compile().as_text()
    entry = text[text.index("ENTRY"):].splitlines()[1:]
    ran = []
    for line in entry:
        if " = " not in line:
            continue
        name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
        op = re.search(r" ([a-z][\w-]*)\(", rest).group(1)
        if op not in _NO_OP:
            ran.append((name, op, rest.split(", metadata=", 1)[0]))
    kernels = [r for r in ran if r[1] == "custom-call"]
    assert len(kernels) == 1 and kernels[0][0].startswith(f"%{key}")
    assert "tpu_custom_call" in kernels[0][2]
    others = [r for r in ran if r is not kernels[0]]
    if program is ops.scores_slots_program:
        # One output, the scores' bits and the slots as the rows of one
        # int32 table, for the host to fetch as one buffer, and after
        # the kernel one plain slice of its padding at every size (none
        # of these sizes fills the 128-node rows of its padded table).
        assert kernels[0][2].startswith("s32[2,")
        assert [op for _, op, _ in others] == ["slice"]
    if n_nodes >= ns.LANE * ns.BLOCK_ROWS:
        assert not [r for r in others if key in r[0] + r[2]]
        assert {op for _, op, _ in ran} == {"custom-call", "slice"}
        return
    # Below one block the slice of the padding changes the tiling of one
    # (8, 128) tile, which XLA fuses with the slice; where no padding is
    # cut off it is a bitcast and runs nothing.  Such a fusion may name
    # the kernel as its operand, which is no name of its own ...
    assert all(op in ("slice", "fusion") and name.startswith("%slice")
               for name, op, _ in others)
    assert not [r for r in others
                if key in r[0] + re.sub(r"\(%[^)]*\)", "()", r[2])]
    # ... and the trace reader, which searches an op's long_name (its
    # instruction with operands) for the kernel's name, does not count
    # it as the kernel.
    for name, _, rest in others:
        event = trace.Event("/device:TPU:0", trace.OPS_LINE, name, 0.0,
                            1.0, {"long_name": f"{name} = {rest}"})
        assert not trace.matches(event, readers.SCORE_KERNEL)


def test_wkv6_compiles_at_rwkv6_3b_widths(one_chip):
    # rwkv6-3b: d_model 2560 = 40 heads x 64; train_4k sequence length.
    B, T, H, n, tb = 1, 4096, 40, 64, 256
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    streams = [f32((B, T, H, n)) for _ in range(4)]
    compiled = wkv6_pallas.lower(*streams, f32((H, n)), f32((B, H, n, n)),
                                 tb=tb).compile()
    assert "tpu_custom_call" in compiled.as_text()
