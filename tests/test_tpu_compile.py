"""Compile the main path's Pallas kernels for a described TPU v5e at real
widths: nothing runs, but Mosaic refuses here what the chip would refuse.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# a smollm-135m gradient (134.5M f32) split over a 4-device fabric, with
# an odd tail so the last grid block runs past the end
GRAD_ELEMS = 33_750_017
# smollm-135m attention at the train shape: batch 8, seq 2048, 9 heads,
# 3 kv heads, head_dim 64, bf16
FLASH_SHAPE = dict(b=8, s=2048, h=9, kv=3, d=64)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read the persistent cache back, so do not
    # write to it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_tree_combine_compiles(one_chip):
    from repro.kernels.tree_combine.kernel import tree_combine
    recv = jax.ShapeDtypeStruct((1, GRAD_ELEMS), jnp.float32,
                                sharding=one_chip)
    part = jax.ShapeDtypeStruct((GRAD_ELEMS,), jnp.float32,
                                sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(tree_combine, recv, part)


@pytest.mark.parametrize("name", ["q8_pack_wire", "q8_combine_wire",
                                  "q8_unpack_wire"])
def test_q8_codec_compiles(one_chip, name):
    from repro.kernels.tree_combine import kernel
    lanes = jax.ShapeDtypeStruct((GRAD_ELEMS,), jnp.float32,
                                 sharding=one_chip)
    wire = jax.ShapeDtypeStruct((GRAD_ELEMS + 4,), jnp.int8,
                                sharding=one_chip)
    scale = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    args = {"q8_pack_wire": (lanes, scale),
            "q8_combine_wire": (wire, lanes),
            "q8_unpack_wire": (wire,)}[name]
    assert "tpu_custom_call" in _compiled_text(getattr(kernel, name), *args)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention
    b, s, h, kv, d = (FLASH_SHAPE[k] for k in ("b", "s", "h", "kv", "d"))
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention, q, k, k)
