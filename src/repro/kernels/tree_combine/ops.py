"""jit'd entry points for the tree-combine and int8 wire-codec kernels.

Dispatch policy: the Pallas kernels run on TPU (and under interpret mode
when explicitly requested), at every payload size; host backends take the
jnp references, which XLA fuses into the surrounding program --
interpret-mode Pallas would be strictly slower there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import (q8_combine_wire, q8_pack_wire, q8_unpack_wire,
                     tree_combine)
from .ref import (q8_combine_ref, q8_pack_ref, q8_pack_rows_ref, q8_scale,
                  q8_unpack_ref, q8_unpack_rows_ref, tree_combine_ref)


def _on_tpu(use_pallas):
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


def _interpret():
    return jax.default_backend() != "tpu"


def combine(recv, partial, *, use_pallas=None):
    if _on_tpu(use_pallas):
        return tree_combine(recv, partial, interpret=_interpret())
    return tree_combine_ref(recv, partial)


def q8_pack(x, scale=None, *, use_pallas=None):
    """Quantize ``x`` into the ``(L+4,) int8`` wire form (payload + scale
    tail).  ``scale`` defaults to :func:`q8_scale` of ``x``."""
    if scale is None:
        scale = q8_scale(x)
    if _on_tpu(use_pallas):
        return q8_pack_wire(x, scale, interpret=_interpret())
    return q8_pack_ref(x, scale)


def q8_combine(wire, partial, *, use_pallas=None):
    """partial + dequantize(wire): the quantize-aware tree combine."""
    if _on_tpu(use_pallas):
        return q8_combine_wire(wire, partial, interpret=_interpret())
    return q8_combine_ref(wire, partial)


def q8_unpack(wire, dtype=None, *, use_pallas=None):
    """Dequantize a wire buffer back to ``dtype`` (default f32)."""
    dtype = jnp.float32 if dtype is None else dtype
    if _on_tpu(use_pallas):
        return q8_unpack_wire(wire, dtype, interpret=_interpret())
    return q8_unpack_ref(wire, dtype)


def q8_pack_rows(x, *, use_pallas=None):
    """Pack every chunk row at once: (k, m) -> (k, m+4) int8 wires (the
    broadcast-phase pack-once point).  On TPU the pack kernel runs once
    per row (a (k, m) block breaks the TPU's (8, 128) tiling rule for
    k < 8); host backends take the row-batched reference."""
    if _on_tpu(use_pallas):
        scales = q8_scale(x, axis=1)
        return jnp.stack([q8_pack_wire(x[j], scales[j],
                                       interpret=_interpret())
                          for j in range(x.shape[0])])
    return q8_pack_rows_ref(x)


def q8_unpack_rows(wires, dtype=None, *, use_pallas=None):
    """Inverse of :func:`q8_pack_rows`: (k, m+4) int8 -> (k, m)."""
    dtype = jnp.float32 if dtype is None else dtype
    if _on_tpu(use_pallas):
        return jnp.stack([q8_unpack_wire(wires[j], dtype,
                                         interpret=_interpret())
                          for j in range(wires.shape[0])])
    return q8_unpack_rows_ref(wires, dtype)
