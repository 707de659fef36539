"""Compile the main path's Pallas kernels for a described TPU v5e at real
widths: nothing runs, but Mosaic refuses here what the chip would refuse.

The topology is described inside a module fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

# a smollm-135m gradient (134.5M f32) split over a 4-device fabric, with
# an odd tail so the last grid block runs past the end
GRAD_ELEMS = 33_750_017
# the whole smollm-135m gradient, as the four-chip EDST sync carries it
SMOLLM_GRAD_ELEMS = 134_515_008
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


_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]\{([\d,]*)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")


def _computations(text):
    """HLO computation name -> {instruction name: its text}."""
    comps = {}
    for block in re.split(r"\n\s*\n", text):
        lines = block.strip().split("\n")
        head = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(", lines[0])
        if head:
            comps[head.group(1)] = dict(m.groups() for m in map(
                _INSTR.match, lines[1:]) if m)
    return comps


def test_pipelined_scan_waves_independent(topo):
    """The (4, 1) fabric's pipelined allreduce of the smollm gradient at
    S = 64, compiled for the chip: the scan's carry keeps the segment
    index outside its two tiled dimensions (a segment is whole tiles),
    the loop body holds one collective-permute per wave, and no wave's
    send reads the carry after another wave's write of the same step."""
    from repro.dist.steps import edst_spec_for_mesh
    from repro.dist.tree_allreduce import pipelined_tree_allreduce
    segments = 64
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    spec = edst_spec_for_mesh((4, 1), ("data", "model"), None,
                              engine="pipelined")
    sync = jax.shard_map(
        lambda v: pipelined_tree_allreduce(v[0], spec,
                                           segments=segments)[None],
        mesh=mesh, in_specs=PartitionSpec("data"),
        out_specs=PartitionSpec("data"))
    grads = jax.ShapeDtypeStruct((4, SMOLLM_GRAD_ELEMS), jnp.float32,
                                 sharding=NamedSharding(
                                     mesh, PartitionSpec("data")))
    comps = _computations(_compiled_text(sync, grads))
    bodies = [c for c in comps.values()
              if any("collective-permute-start(" in v for v in c.values())]
    assert len(bodies) == 1
    body = bodies[0]

    # the carry: the largest array the loop passes from step to step
    param = next(v for v in body.values() if " parameter(0)" in v)
    dims, layout = max(
        (([int(d) for d in shape.split(",") if d],
          [int(d) for d in order.split(",") if d])
         for shape, order in _ARRAY.findall(param)),
        key=lambda a: math.prod(a[0]))
    seg_dims = [i for i, d in enumerate(dims) if d in (segments,
                                                       segments + 1)]
    assert seg_dims and not set(seg_dims) & set(layout[:2]), (dims, layout)

    starts = [n for n, v in body.items() if "collective-permute-start(" in v]
    assert len(starts) == len(spec.waves)

    carry = "f32[%s]" % ",".join(map(str, dims))

    def writes_carry(name):
        text = body[name]
        return text.startswith(carry) and not re.search(
            r"\b(parameter|get-tuple-element)\(", text)

    for start in starts:
        seen, todo = set(), [start]
        while todo:
            for op in re.findall(r"%([\w.\-]+)", body[todo.pop()]):
                if op in body and op not in seen:
                    assert not writes_carry(op), (start, op)
                    seen.add(op)
                    todo.append(op)
