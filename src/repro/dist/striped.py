"""Striped EDST collectives: reduce-scatter, allgather, and the composed
bandwidth-optimal allreduce, executed with ``ppermute`` under ``shard_map``.

The engines in :mod:`repro.dist.tree_allreduce` ship the full m-sized
chunk along every tree edge.  This module executes the
:class:`repro.core.collectives.StripedCollectiveSpec` program instead:
each vertex owns one stripe of every tree's chunk (DFS-preorder slots,
largest-remainder ``chunk_sizes`` widths), reduce-scatter waves move
partial sums so every edge carries only the stripes owned on the far
side of it, and allgather waves fan the finished stripes back out as a
pure gather.  Per-wave wire bytes drop from ``m`` to
``ceil(m/n) * slots-in-window`` at roughly twice the wave count -- the
win on bandwidth-dominated fabrics, the loss on alpha-dominated hosts
(see the engine-selection matrix in ``src/repro/dist/README.md``).

Execution model: state is the ``(k, mrow)`` stack of padded chunk rows.
Every window is one *circular* interval of a row (the preorder trick:
a subtree and its complement are both contiguous mod n), so a wave needs
only ``(n,)``-shaped offset/length tables -- a sender rolls its row and
slices the wave's wire width, a receiver rolls the zero-padded arrival
back into place and either accumulates (reduce-scatter) or overwrites
(allgather) under a circular mask.  Weighted fractions reuse the SAME
slot->offset table over the padded width ``mrow``: padding elements are
zero everywhere, so reducing and gathering them is harmless, and
degraded (k-1)-striping shares the healthy program's wave structure.

With ``quantize=True`` reduce-scatter hops obey the ``codec`` policy
(int8 wire via the Pallas codec in ``repro.kernels.tree_combine``, one
collective per hop) and allgather hops always take the int8 wire when
the codec is enabled -- each hop re-codes, since unlike the broadcast
phase of the chunk engines the gathered windows differ hop to hop.

Everything this executor relies on -- op-homogeneous ppermute-legal
waves, window/tree agreement between sender and receiver, circular
complement of below/above windows, child-window nesting, RS-then-AG
happens-before -- is provable from the spec's tables alone and IS
proved, statically, by :mod:`repro.analysis.verify` (see the "Static
invariants" section of ``src/repro/dist/README.md``); spec compilation
already ran the cheap tier via ``verify_compiled_spec``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.collectives import (StripedCollectiveSpec, REDUCE,
                                striped_tables)
from .tree_allreduce import (_FLOATS, _REDUCE_WIRE, _axis_arg, _gather,
                             _note_trace, _rows_of, _rows_out,
                             _send, resolve_codec)


def _normalize(fractions):
    return None if fractions is None else tuple(fractions)


def _wires(quantize: bool, codec, dtype) -> tuple:
    """(reduce-scatter wire, allgather wire) for the codec policy."""
    codec = resolve_codec(codec) if quantize else "off"
    if dtype not in _FLOATS:
        codec = "off"       # integer payloads always travel verbatim
    return _REDUCE_WIRE[codec], ("q8" if codec != "off" else None)


def _rows_in(flat, sizes, mrow):
    """Stack the per-tree chunk slices into the padded (k, mrow) state
    (the shared ``_rows_of`` splitter from ``tree_allreduce``)."""
    return jnp.stack(_rows_of(flat, len(sizes), sizes, mrow))


def _run_wave(state, bw, idx, axis, rs_wire, ag_wire):
    """Execute ONE bound striped wave on the (k, mrow) state.

    Non-senders compute a (discarded) payload and non-receivers carry a
    zero-length mask, so the whole wave is branch-free; ``ppermute``
    hands devices nobody sent to a zero payload, which the circular mask
    drops anyway.  The loop body of :func:`_run_waves`."""
    k, mrow = state.shape
    pos = jnp.arange(mrow)
    rows_iota = jnp.arange(k)
    src_tree = _gather(bw.send_tree, idx)
    src_off = _gather(bw.send_off, idx)
    row = jax.lax.dynamic_index_in_dim(state, src_tree, 0,
                                       keepdims=False)
    payload = jnp.roll(row, -src_off)[:bw.wire]
    recv = _send(payload, axis, bw.perm,
                 rs_wire if bw.op == REDUCE else ag_wire)
    roff = _gather(bw.recv_off, idx)
    rlen = _gather(bw.recv_len, idx)
    rtree = _gather(bw.recv_tree, idx)
    full = recv if bw.wire == mrow \
        else jnp.pad(recv, (0, mrow - bw.wire))
    rolled = jnp.roll(full, roff)
    mask = jnp.roll(pos < rlen, roff)      # circular window, len 0 = none
    onehot = rows_iota == rtree
    if bw.op == REDUCE:
        contrib = jnp.where(mask, rolled, jnp.zeros((), rolled.dtype))
        return state + onehot.astype(state.dtype)[:, None] \
            * contrib[None, :]
    sel = onehot[:, None] & mask[None, :]
    return jnp.where(sel, rolled[None, :], state)


def _run_waves(state, waves, idx, axis, rs_wire, ag_wire):
    """Execute bound striped waves on the (k, mrow) state."""
    for w, bw in enumerate(waves):
        op = "rs" if bw.op == REDUCE else "ag"
        with jax.named_scope(f"edst/t*/w{w}/{op}"):
            state = _run_wave(state, bw, idx, axis, rs_wire, ag_wire)
    return state


def _prep(x, spec, fractions):
    axis = _axis_arg(spec)
    idx = jax.lax.axis_index(axis)
    flat = x.reshape(-1)
    bound = striped_tables(spec, flat.size, _normalize(fractions))
    return axis, idx, flat, bound


def _cut_own(state, spec, bound, idx):
    """Cut this vertex's own stripe out of every (k, mrow) row (circular
    windows never wrap for a single slot, so one roll + static slice
    suffices); rows are zero-padded to the widest stripe ``smax``."""
    own = []
    for j in range(spec.k):
        off = _gather(bound.own_off[j], idx)
        length = _gather(bound.own_len[j], idx)
        stripe = jnp.roll(state[j], -off)[:bound.smax]
        own.append(jnp.where(jnp.arange(bound.smax) < length, stripe,
                             jnp.zeros((), stripe.dtype)))
    return jnp.stack(own)


def tree_reduce_scatter(x, spec: StripedCollectiveSpec, fractions=None,
                        quantize: bool = False, codec=None):
    """Reduce-scatter of ``x`` over ``spec.axes``: returns the
    ``(k, smax)`` stack of THIS vertex's owner stripes, each row the
    globally-summed stripe of one tree's chunk, zero-padded to the
    widest stripe.  Stripe geometry (offset/width per tree) comes from
    :func:`stripe_layout`.  Must run inside a ``shard_map`` whose manual
    axes include ``spec.axes``."""
    if spec.k == 0 or x.size == 0:
        return x
    axis, idx, flat, bound = _prep(x, spec, fractions)
    rs_wire, _ = _wires(quantize, codec, x.dtype)
    state = _rows_in(flat, bound.sizes, bound.mrow)
    state = _run_waves(state, bound.rs_waves, idx, axis, rs_wire, None)
    return _cut_own(state, spec, bound, idx)


def stripe_slices(x, spec: StripedCollectiveSpec, fractions=None):
    """This vertex's ``(k, smax)`` owner stripes of a REPLICATED array
    ``x`` -- the same cut :func:`tree_reduce_scatter` applies after its
    reduce waves, with zero communication.  The ZeRO-1 train step uses
    it to slice the (replicated) params and weight-decay mask into the
    scattered domain the sharded optimizer updates in.  Must run inside
    a ``shard_map`` whose manual axes include ``spec.axes``."""
    if spec.k == 0 or x.size == 0:
        return x
    _, idx, flat, bound = _prep(x, spec, fractions)
    state = _rows_in(flat, bound.sizes, bound.mrow)
    return _cut_own(state, spec, bound, idx)


def tree_allgather(owned, spec: StripedCollectiveSpec, shape,
                   fractions=None, quantize: bool = False, codec=None):
    """Allgather of owner stripes: the inverse of
    :func:`tree_reduce_scatter`.  ``owned`` is the ``(k, smax)`` stack
    of this vertex's stripes; returns the full ``shape``-d array (every
    stripe of every tree, replicated across the fabric).  Must run
    inside a ``shard_map`` whose manual axes include ``spec.axes``."""
    if spec.k == 0:
        return owned
    size = 1
    for d in shape:
        size *= int(d)
    axis = _axis_arg(spec)
    idx = jax.lax.axis_index(axis)
    bound = striped_tables(spec, size, _normalize(fractions))
    _, ag_wire = _wires(quantize, codec, owned.dtype)
    rows = []
    for j in range(spec.k):
        off = _gather(bound.own_off[j], idx)
        length = _gather(bound.own_len[j], idx)
        stripe = jnp.where(jnp.arange(bound.smax) < length, owned[j],
                           jnp.zeros((), owned.dtype))
        full = stripe if bound.smax == bound.mrow \
            else jnp.pad(stripe, (0, bound.mrow - bound.smax))
        rows.append(jnp.roll(full, off))
    state = jnp.stack(rows)
    state = _run_waves(state, bound.ag_waves, idx, axis, None, ag_wire)
    return _rows_out(state, bound.sizes, size).reshape(shape)


def striped_allreduce(x, spec: StripedCollectiveSpec, quantize: bool = False,
                      fractions=None, codec=None):
    """Allreduce (sum) of the per-device array ``x`` over ``spec.axes``
    as reduce-scatter ∘ allgather on the COMPOSED wave program (one DAG:
    a shallow tree's gather overlaps a deep tree's scatter tail).
    Returns the summed array in the original shape, replicated across
    the fabric.  Must run inside a ``shard_map`` whose manual axes
    include ``spec.axes``."""
    if spec.k == 0 or x.size == 0:
        return x
    if fractions is not None and len(fractions) != spec.k:
        raise ValueError(f"{len(fractions)} fractions for k={spec.k} trees; "
                         "spec and striping must come from the same schedule")
    _note_trace("striped", spec, x,
                codec=(resolve_codec(codec) if quantize else None),
                fractions=fractions)
    shape, dtype = x.shape, x.dtype
    axis, idx, flat, bound = _prep(x, spec, fractions)
    rs_wire, ag_wire = _wires(quantize, codec, dtype)
    state = _rows_in(flat, bound.sizes, bound.mrow)
    state = _run_waves(state, bound.waves, idx, axis, rs_wire, ag_wire)
    return _rows_out(state, bound.sizes, flat.size) \
        .reshape(shape).astype(dtype)


def stripe_layout(spec: StripedCollectiveSpec, size: int, fractions=None):
    """The bound stripe geometry for a payload of ``size`` elements:
    the :class:`repro.core.collectives.StripedTables` whose ``sizes`` /
    ``offsets`` / ``own_off`` / ``own_len`` describe exactly how
    :func:`tree_reduce_scatter` apportions ownership."""
    return striped_tables(spec, size, _normalize(fractions))


def rs_conservation_gap(flat_reduced, owned, axis):
    """In-graph integrity check for the scattered domain (the striped /
    ZeRO-1 engines never replicate, so :func:`repro.dist.health
    .replication_divergence` does not apply): after a reduce-scatter the
    owner stripes across the fabric must partition the reduced vector,
    so the global sum of owned elements must equal the global sum of the
    (per-device mean-contribution) payload.  Returns the RELATIVE gap
    ``|sum(owned) - sum(reduced)| / (|sum(reduced)| + 1)`` -- ~1e-7 of
    float reassociation noise when healthy, O(magnitude) when a wire
    corrupted, duplicated, or dropped a stripe.  Two scalar ``psum``\\ s;
    call it inside the same ``shard_map`` as the reduce-scatter, passing
    ``flat_reduced`` as this device's contribution ALREADY divided by
    the fabric size (so its psum is the reduced vector's sum)."""
    a = jax.lax.psum(jnp.sum(flat_reduced.astype(jnp.float32)), axis)
    b = jax.lax.psum(jnp.sum(owned.astype(jnp.float32)), axis)
    return jnp.abs(b - a) / (jnp.abs(a) + 1.0)
