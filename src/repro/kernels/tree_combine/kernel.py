"""Pallas TPU kernels for the EDST tree collectives: the multi-child
partial-sum combine and the int8 wire codec.

``tree_combine``: out = partial + sum_over_children(recv) over a length-L
flat buffer, tiled so each grid step streams one (children, tile) block
through VMEM.  f32 accumulation regardless of payload dtype (gradient
chunks are bf16 on the wire when quantization is off).

``q8_pack_wire`` / ``q8_combine_wire`` / ``q8_unpack_wire``: the quantized
wire format is ``(L + 4,) int8`` -- L quantized lanes followed by the
per-chunk f32 scale bit-packed into a 4-byte tail, so a quantized hop is
ONE ppermute payload.  Pack (quantize), unpack+accumulate (dequantize
fused into the partial-sum add) and plain unpack each stream the lanes
through one gridded kernel, at any payload size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(recv_ref, part_ref, o_ref):
    acc = part_ref[...].astype(jnp.float32)
    acc = acc + jnp.sum(recv_ref[...].astype(jnp.float32), axis=0)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def tree_combine(recv, partial, *, tile=65536, interpret=False):
    """recv: (n_children, L); partial: (L,) -> (L,)."""
    nch, l = recv.shape
    tl = min(tile, l)
    l_pad = -(-l // tl) * tl
    if l_pad != l:
        recv = jnp.pad(recv, ((0, 0), (0, l_pad - l)))
        partial = jnp.pad(partial, (0, l_pad - l))

    out = pl.pallas_call(
        _combine_kernel,
        grid=(l_pad // tl,),
        in_specs=[
            pl.BlockSpec((nch, tl), lambda i: (0, i)),
            pl.BlockSpec((tl,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((tl,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((l_pad,), partial.dtype),
        interpret=interpret,
    )(recv, partial)
    return out[:l]


# ---------------------------------------------------------------------------
# int8 wire codec
# ---------------------------------------------------------------------------
#
# Mosaic cannot bitcast between bit widths inside a kernel, so the 4-byte
# scale tail of the wire is written and read by XLA (a 4-byte update or
# slice of the wire buffer, no copy of the lanes) and the kernels take the
# scale -- or its reciprocal, computed by the same XLA op as the
# reference's -- as an SMEM scalar.  Each kernel is a 1D grid over
# ``tile``-lane blocks of the L lanes.  A last block that runs past L
# reads lanes it never stores, so the grid needs no padding copy and
# serves every payload size.

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_LANES = 128


def _lane_grid(l, tile):
    """(block, steps): blocks of ``tile`` lanes, or one block of L
    rounded up to whole vector lanes when L is smaller."""
    tl = min(tile, -(-l // _LANES) * _LANES)
    return tl, pl.cdiv(l, tl)


def _lanes(tl):
    return pl.BlockSpec((tl,), lambda i: (i,))


def _q8_pack_kernel(inv_ref, x_ref, o_ref):
    # |x| <= 127 * scale by construction of the scale, so no clip needed
    o_ref[...] = jnp.round(x_ref[...].astype(jnp.float32)
                           * inv_ref[0]).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def q8_pack_wire(x, scale, *, tile=1 << 18, interpret=False):
    """x: (L,) float, scale: () f32 with max|x| <= 127*scale -> (L+4,) int8
    wire buffer (quantized lanes + bit-packed scale tail)."""
    (l,) = x.shape
    scale = scale.astype(jnp.float32)
    tl, steps = _lane_grid(l, tile)
    wire = pl.pallas_call(
        _q8_pack_kernel,
        grid=(steps,),
        in_specs=[_SMEM, _lanes(tl)],
        out_specs=_lanes(tl),
        out_shape=jax.ShapeDtypeStruct((l + 4,), jnp.int8),
        interpret=interpret,
    )((1.0 / scale).reshape(1), x)
    return wire.at[l:].set(jax.lax.bitcast_convert_type(scale, jnp.int8))


def _wire_scale(wire):
    return jax.lax.bitcast_convert_type(wire[-4:], jnp.float32).reshape(1)


def _q8_combine_kernel(s_ref, w_ref, part_ref, o_ref):
    o_ref[...] = (part_ref[...].astype(jnp.float32)
                  + w_ref[...].astype(jnp.float32) * s_ref[0]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def q8_combine_wire(wire, partial, *, tile=1 << 18, interpret=False):
    """partial + dequantize(wire): the quantize-aware combine, dequantize
    and accumulate fused into one pass over the lanes."""
    (l,) = partial.shape
    tl, steps = _lane_grid(l, tile)
    return pl.pallas_call(
        _q8_combine_kernel,
        grid=(steps,),
        in_specs=[_SMEM, _lanes(tl), _lanes(tl)],
        out_specs=_lanes(tl),
        out_shape=jax.ShapeDtypeStruct((l,), partial.dtype),
        interpret=interpret,
    )(_wire_scale(wire), wire, partial)


def _q8_unpack_kernel(s_ref, w_ref, o_ref):
    o_ref[...] = (w_ref[...].astype(jnp.float32) * s_ref[0]
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "tile", "interpret"))
def q8_unpack_wire(wire, dtype=jnp.float32, *, tile=1 << 18,
                   interpret=False):
    """Plain dequantize of a wire buffer (the broadcast-phase epilogue)."""
    l = wire.shape[0] - 4
    tl, steps = _lane_grid(l, tile)
    return pl.pallas_call(
        _q8_unpack_kernel,
        grid=(steps,),
        in_specs=[_SMEM, _lanes(tl)],
        out_specs=_lanes(tl),
        out_shape=jax.ShapeDtypeStruct((l,), dtype),
        interpret=interpret,
    )(_wire_scale(wire), wire)
