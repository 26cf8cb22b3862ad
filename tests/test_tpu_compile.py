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
from bench import readers  # noqa: E402


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


def _weights(pod_gpus):
    w = E_BINPACK
    return dict(request=pod_gpus, gpus_per_node=8, w_used=w.used,
                w_fit=w.fit, w_group=w.group, w_topo=w.topo)


# Instructions that run no device op of their own.
_NO_OP = ("parameter", "get-tuple-element", "bitcast", "tuple")


@pytest.mark.parametrize("pod_gpus", [1, 2, 4, 8])
@pytest.mark.parametrize("n_nodes", [128, 10_000, 1_000_000])
def test_score_call_is_one_program_with_one_named_kernel(
        one_chip, n_nodes, pod_gpus):
    """The score call's program, on the tables it is staged into and for
    each pod size the cells compile, holds one Mosaic kernel named as
    ``bench/readers.py`` finds it (``SCORE_KERNEL``), reading both
    tables where they were handed in, and after it one plain slice of
    its padding (none of these sizes fills the 128-node rows of its
    padded table).  The kernel has one output, the scores' bits and the
    slots as the rows of one int32 table, for the host to fetch as one
    buffer.  No other instruction names the kernel, operands included,
    and none copies a table before it: a trace finds the kernel's time
    by that name, and the kernel's time must hold its reads of the
    table."""
    key = readers.SCORE_KERNEL
    rows = ops.padded_nodes(n_nodes) // ns.LANE
    tables = [jax.ShapeDtypeStruct((k, rows, ns.LANE), dt, sharding=one_chip)
              for k, dt in ((3, jnp.int32), (2, jnp.float32))]
    text = ops.scores_slots_program.lower(
        *tables, n=n_nodes, **_weights(pod_gpus)).compile().as_text()
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
    assert kernels[0][2].startswith("s32[2,")
    others = [r for r in ran if r is not kernels[0]]
    assert [op for _, op, _ in others] == ["slice"]
    assert not [r for r in others if key in r[0] + r[2]]


def test_wkv6_compiles_at_rwkv6_3b_widths(one_chip):
    # rwkv6-3b: d_model 2560 = 40 heads x 64; train_4k sequence length.
    B, T, H, n, tb = 1, 4096, 40, 64, 256
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    streams = [f32((B, T, H, n)) for _ in range(4)]
    compiled = wkv6_pallas.lower(*streams, f32((H, n)), f32((B, H, n, n)),
                                 tb=tb).compile()
    assert "tpu_custom_call" in compiled.as_text()
