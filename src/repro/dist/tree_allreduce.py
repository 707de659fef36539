"""k-tree allreduce under ``shard_map`` (the paper's Sec. 1.1 payoff, run).

Three executors share this module:

  * the **pipelined segmented** executor (:func:`pipelined_tree_allreduce`,
    the default engine) consumes a :class:`repro.core.collectives.
    PipelinedAllreduceSpec`: the dependency-DAG list schedule packs every
    tree's messages -- both phases -- into the fewest ppermute-legal
    waves, and the payload streams down the trees in S segments so wave w
    moves segment ``t - w`` at step t.  ``segments="auto"`` picks S from
    the :class:`repro.core.collectives.CostModel` calibrated for the
    backend (alpha-dominated hosts unroll S=1; bandwidth-dominated
    fabrics stream ``(waves + S - 1) * (m/S)``), and S > 1 executes as a
    ``jax.lax.fori_loop`` over the step index so HLO size and trace time
    stay flat in S * depth;
  * the **fused global-round** executor (:func:`fused_tree_allreduce`)
    consumes a :class:`repro.core.collectives.FusedAllreduceSpec`: round
    r of every tree merged into shared waves over a stacked ``(k, m)``
    state.  Kept as the round-aligned A/B baseline;
  * the **per-tree** executor (:func:`run_tree_program`, via a
    :class:`TreeAllreduceSpec`) lowers each tree as its own serial
    ppermute chain -- the original baseline.

Vertex ids are the row-major flattened index over the mesh axes being
reduced (``jax.lax.axis_index(axes)``), which matches how
``repro.core.topologies.device_topology`` numbers the fabric.

``ppermute`` needs unique sources *and* destinations per call, so fan-in
and fan-out are statically split into waves by the schedule compilers;
the tree semantics are unchanged (reduction is associative, broadcast
idempotent).  ``ppermute`` hands devices nobody sent to a zero payload,
which the executors exploit: a wave whose every arrival accumulates into
one chunk row is a single unmasked add.

With ``quantize=True`` hops ship int8 chunks with the per-chunk f32 scale
bit-packed into a 4-byte payload tail (one collective per hop, ~4x fewer
wire bytes for f32), through the fused Pallas codec in
``repro.kernels.tree_combine``.  The codec is phase-aware: the broadcast
phase quantizes each tree's total ONCE and forwards the packed bytes down
the tree (one codec invocation amortized over depth hops, and a single
quantization error instead of one per hop).  Reduce hops must re-code per
hop (partials accumulate in f32), so their wire obeys the ``codec``
policy: ``"full"`` compresses them too (the default where bandwidth
dominates, i.e. real fabrics), ``"bcast"`` leaves them f32 (the default
on alpha-dominated host backends, where per-hop codec work costs more
than the wire bytes it saves).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.collectives import (CostModel, FusedAllreduceSpec,
                                PipelinedAllreduceSpec,
                                StripedCollectiveSpec, chunk_sizes,
                                verify_compiled_spec, wave_wire_bytes)
from ..kernels.tree_combine.ops import (combine, q8_combine, q8_pack,
                                        q8_pack_rows, q8_unpack,
                                        q8_unpack_rows)
from ..telemetry import metrics as _metrics


# ---------------------------------------------------------------------------
# static spec (per-tree baseline form)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TreeProgram:
    """One tree's rounds, each a tuple of (src, dst) pairs with unique
    sources and destinations (ppermute-legal).  ``bcast_dst[r][v]`` is the
    precompiled is-destination table of broadcast round r -- built once at
    spec-compile time, not per executor call."""
    root: int
    reduce_rounds: tuple
    bcast_rounds: tuple
    bcast_dst: tuple = ()   # tuple[tuple[bool, ...]] aligned with bcast_rounds


@dataclass(frozen=True)
class TreeAllreduceSpec:
    n: int                 # fabric size = product of the reduced axis sizes
    axes: tuple            # mesh axis names the allreduce runs over
    trees: tuple           # tuple[TreeProgram]

    @property
    def k(self) -> int:
        return len(self.trees)

    @property
    def depth(self) -> int:
        return max((len(t.bcast_rounds) for t in self.trees), default=0)


def _split_unique(msgs):
    """Partition one round's (src, dst) messages into ppermute-legal
    sub-rounds: within a sub-round no vertex repeats as src or as dst."""
    out = []
    remaining = list(msgs)
    while remaining:
        srcs, dsts, taken, rest = set(), set(), [], []
        for s, d in remaining:
            if s in srcs or d in dsts:
                rest.append((s, d))
            else:
                srcs.add(s)
                dsts.add(d)
                taken.append((s, d))
        out.append(tuple(taken))
        remaining = rest
    return out


def _compile_rounds(rounds):
    out = []
    for msgs in rounds:
        out.extend(_split_unique(msgs))
    return tuple(out)


def _dst_tables(rounds, n: int):
    out = []
    for perm in rounds:
        table = [False] * n
        for _, d in perm:
            table[d] = True
        out.append(tuple(table))
    return tuple(out)


def spec_from_schedule(sched, axis_names, verify=None) -> TreeAllreduceSpec:
    """Compile an :class:`repro.core.collectives.AllreduceSchedule` into a
    static per-tree spec bound to the given mesh axis names.  (The fused
    and pipelined forms come from ``repro.core.collectives``.)  Like
    those compilers, the fresh spec is statically verified per
    ``verify=`` (``repro.analysis.verify``; level resolved from
    ``REPRO_VERIFY_SPECS``) before being returned."""
    trees = []
    for ts in sched.trees:
        bcast = _compile_rounds(ts.bcast_rounds)
        trees.append(TreeProgram(root=ts.root,
                                 reduce_rounds=_compile_rounds(ts.reduce_rounds),
                                 bcast_rounds=bcast,
                                 bcast_dst=_dst_tables(bcast, sched.n)))
    spec = TreeAllreduceSpec(n=sched.n, axes=tuple(axis_names),
                             trees=tuple(trees))
    return verify_compiled_spec(spec, verify, "spec_from_schedule")


# chunk apportioning: the canonical largest-remainder helper lives in
# repro.core.collectives (owner-stripe assignment needs it at the core
# layer); imported above and re-exported here because the executors and
# repro.dist.fault historically import it from this module.


# ---------------------------------------------------------------------------
# wire codec policy (shared by all executors)
# ---------------------------------------------------------------------------

def _axis_arg(spec):
    return spec.axes[0] if len(spec.axes) == 1 else tuple(spec.axes)

def resolve_codec(codec=None) -> str:
    """The quantized-wire policy:

      * ``"full"`` -- int8 + scale tail on every hop, through the fused
        Pallas codec; the broadcast phase packs each tree's total ONCE
        and forwards the wire verbatim.  4x fewer wire bytes: the
        default where bandwidth dominates, i.e. real fabrics;
      * ``"hybrid"`` -- bf16 reduce wires (f32 accumulation), int8
        pack-once broadcast: 2x/4x fewer bytes at two casts per reduce
        hop;
      * ``"bcast"`` -- f32 reduce wires, int8 pack-once broadcast only;
      * ``"off"`` -- no compression: ``quantize=True`` compiles the
        identical program as ``quantize=False``.

    ``"auto"`` resolves by the same calibration as the segment
    autotuner: on alpha-dominated host backends every codec variant was
    measured slower than shipping f32 (the per-op dispatch of
    quantize/dequantize -- and bf16's software emulation -- costs more
    than the wire bytes saved, at every payload size), so compression is
    model-disabled there; bandwidth-dominated backends take ``"full"``.
    """
    if codec in (None, "auto"):
        # same split as CostModel.for_backend: only the serialized-
        # collective "cpu" host disables compression; GPU/TPU fabrics
        # take the full int8 wire
        return "off" if jax.default_backend() == "cpu" else "full"
    if codec not in ("full", "hybrid", "bcast", "off"):
        raise ValueError(f"codec {codec!r} not in "
                         "('auto', 'full', 'hybrid', 'bcast', 'off')")
    return codec


_REDUCE_WIRE = {"full": "q8", "hybrid": "bf16", "bcast": None, "off": None}

_FLOATS = (jnp.float32, jnp.bfloat16, jnp.float16)

# one (8, 128) tile of 32-bit words, the unit of the TPU's array layout
_TILE_BYTES = 4096
_LANES = 128


# ---------------------------------------------------------------------------
# wave-level observability (shared by all executors)
# ---------------------------------------------------------------------------

def _wave_label(w: int, wv) -> str:
    """``edst/t{tree}/w{wave}/{op}`` for a pipelined wave: the tree when
    the wave ships a single chunk row, ``t*`` for merged waves."""
    tree = f"t{wv.rows[0]}" if len(wv.rows) == 1 else "t*"
    red = bool(np.any(wv.reduce_flag))
    bc = bool(np.any(wv.bcast_flag))
    op = "mixed" if red and bc else ("reduce" if red else "bcast")
    return f"edst/{tree}/w{w}/{op}"


def _note_trace(engine: str, spec, x, codec=None, fractions=None,
                segments=None) -> None:
    """Executor-entry metrics hook.  Inside ``jit`` this Python runs at
    trace time only, so it counts compiled program traces (the retrace
    detector), not steps -- and costs nothing per step.  ``segments``
    is the pipelined engine's compiled segment count."""
    try:
        itemsize = jnp.dtype(x.dtype).itemsize
        wires = wave_wire_bytes(spec, x.size * itemsize, itemsize, fractions)
        _metrics.note_program(engine, getattr(spec, "key", None) or spec,
                              waves=len(wires), wire_bytes=sum(wires),
                              codec=codec, segments=segments)
    except Exception:       # pragma: no cover - telemetry never breaks a step
        pass


def _pack_wire32(x):
    """Quantize chunk rows into an f32-lane wire: ``(..., m) float ->
    (..., ceil(m/4) + 1) f32`` holding the int8 payload bit-packed four
    to a lane plus the scale lane.  The broadcast phase forwards THIS
    form: every gather/mask op and every hop then touches 4x fewer
    elements than the unpacked rows, and zero-filled ppermute arrivals
    decode to exact zeros (zero scale)."""
    m = x.shape[-1]
    pad = -m % 4
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    w8 = q8_pack_rows(x) if x.ndim == 2 else q8_pack(x)
    return jax.lax.bitcast_convert_type(
        w8.reshape(*w8.shape[:-1], -1, 4), jnp.float32)


def _unpack_wire32(w32, dtype, m):
    """Inverse of :func:`_pack_wire32` back to ``(..., m)`` rows."""
    w8 = jax.lax.bitcast_convert_type(w32, jnp.int8)
    w8 = w8.reshape(*w8.shape[:-2], -1)
    out = q8_unpack_rows(w8, dtype) if w8.ndim == 2 else q8_unpack(w8, dtype)
    return out[..., :m]


def _acc(partial, update):
    """Reduce accumulation: through the Pallas tree-combine (f32 on-chip
    accumulation) for float gradients on TPU, a plain add elsewhere."""
    if jax.default_backend() == "tpu" and partial.dtype in (
            jnp.float32, jnp.bfloat16, jnp.float16):
        return combine(update[None, :], partial)
    return partial + update


def _send(x, axis, perm, wire=None):
    """ppermute a chunk; devices nobody sends to receive zeros.  ``wire``
    compresses the hop: ``"q8"`` ships int8 with the f32 scale packed in
    its tail (one collective per hop, 4x fewer bytes for f32), ``"bf16"``
    casts on and off the wire (2x fewer bytes).  Integer payloads always
    travel verbatim -- compression would corrupt them."""
    if wire is not None and x.dtype not in _FLOATS:
        wire = None
    if wire == "q8":
        w = jax.lax.ppermute(q8_pack(x), axis, list(perm))
        return q8_unpack(w, x.dtype)
    if wire == "bf16":
        return jax.lax.ppermute(x.astype(jnp.bfloat16), axis,
                                list(perm)).astype(x.dtype)
    return jax.lax.ppermute(x, axis, list(perm))


# ---------------------------------------------------------------------------
# per-tree execution (inside shard_map) -- the A/B baseline
# ---------------------------------------------------------------------------

def run_tree_program(c, tree: TreeProgram, n: int, axis,
                     quantize: bool = False, codec=None,
                     scope_tree: int = 0):
    """Reduce chunk ``c`` up ``tree`` and broadcast the total back down.

    The per-tree building block: tree j's whole chain completes before
    tree j+1 starts in program order.  Kept for the executor A/B
    benchmark; the pipelined executor below is the default engine.
    ``scope_tree`` only names the profiler scopes (``edst/t{j}/...``).
    """
    codec = resolve_codec(codec) if quantize else "off"
    wire = _REDUCE_WIRE[codec]
    idx = jax.lax.axis_index(axis)
    # reduce: every non-root sends its accumulated value to its parent
    # exactly once, deepest level first, so parents accumulate complete
    # subtree sums before forwarding
    for w, perm in enumerate(tree.reduce_rounds):
        with jax.named_scope(f"edst/t{scope_tree}/w{w}/reduce"):
            c = c + _send(c, axis, perm, wire)
    # broadcast: the root's total overwrites down the levels.  Quantized,
    # the total is packed ONCE and the int8 wire forwards verbatim.
    if not tree.bcast_rounds:
        return c
    base = len(tree.reduce_rounds)
    if codec != "off" and c.dtype in _FLOATS:
        packed = _pack_wire32(c)
        for w, (perm, table) in enumerate(zip(tree.bcast_rounds,
                                              tree.bcast_dst)):
            with jax.named_scope(f"edst/t{scope_tree}/w{base + w}/bcast"):
                recv = jax.lax.ppermute(packed, axis, list(perm))
                packed = jnp.where(jnp.asarray(table)[idx], recv, packed)
        return _unpack_wire32(packed, c.dtype, c.shape[0])
    for w, (perm, table) in enumerate(zip(tree.bcast_rounds,
                                          tree.bcast_dst)):
        with jax.named_scope(f"edst/t{scope_tree}/w{base + w}/bcast"):
            recv = jax.lax.ppermute(c, axis, list(perm))
            c = jnp.where(jnp.asarray(table)[idx], recv, c)
    return c


def per_tree_allreduce(x, spec: TreeAllreduceSpec, quantize: bool = False):
    """Allreduce (sum) of ``x`` over ``spec.axes``, one serial ppermute
    chain per tree (the pre-fusion executor)."""
    if spec.k == 0:
        return x
    _note_trace("per_tree", spec, x,
                codec=resolve_codec(None) if quantize else None)
    axis = _axis_arg(spec)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % spec.k
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(spec.k, -1)

    outs = [run_tree_program(chunks[j], tree, spec.n, axis, quantize,
                             scope_tree=j)
            for j, tree in enumerate(spec.trees)]

    out = jnp.concatenate(outs) if spec.k > 1 else outs[0]
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# fused global-round execution (inside shard_map) -- round-aligned baseline
# ---------------------------------------------------------------------------

def _wave_rows(rnd):
    """Static (senders' rows, receivers' rows) of one wave.  Single-row
    waves (every message from the same tree -- common, since fan-in
    splits produce them) specialize to static indexing below."""
    srcs = np.array([s for s, _ in rnd.perm], np.int64)
    dsts = np.array([d for _, d in rnd.perm], np.int64)
    return (np.unique(rnd.send_row[srcs]), np.unique(rnd.recv_row[dsts]))


def _fused_send(chunks, rnd, idx, axis, wire=None):
    """One wave: every vertex ships the chunk row its table says, the
    single ppermute moves all trees' round-r traffic at once, and the
    receive tables say where (and whether) the arrival lands."""
    send_rows, recv_rows = _wave_rows(rnd)
    if chunks.ndim == 1:
        payload = chunks
    elif len(send_rows) == 1:
        payload = chunks[int(send_rows[0])]
    else:
        payload = chunks[jnp.asarray(rnd.send_row)[idx]]
    recv = _send(payload, axis, rnd.perm, wire)
    flag = jnp.asarray(rnd.recv_flag)[idx]
    return recv, flag, recv_rows


def fused_tree_allreduce(x, spec: FusedAllreduceSpec, quantize: bool = False,
                         fractions=None, codec=None):
    """Allreduce (sum) of the per-device array ``x`` over ``spec.axes``
    with the fused global-round program.

    Must run inside a ``shard_map`` whose manual axes include
    ``spec.axes``.  ``x`` is flattened and striped into k chunk rows
    (uniform split, or ``chunk_sizes(size, fractions)`` when weighted
    striping is requested); rows are padded to a common width so the
    stacked ``(k, m)`` state ships through shared waves.  Single-tree
    specs skip the row stacking/indexing machinery entirely and run on
    the flat chunk.  Returns the summed array in the original shape
    (replicated across the fabric).
    """
    if spec.k == 0 or x.size == 0:
        return x
    if fractions is not None and len(fractions) != spec.k:
        raise ValueError(f"{len(fractions)} fractions for k={spec.k} trees; "
                         "spec and striping must come from the same schedule")
    codec = resolve_codec(codec) if quantize else "off"
    _note_trace("fused", spec, x, codec=codec if quantize else None,
                fractions=fractions)
    r_wire = _REDUCE_WIRE[codec]
    axis = _axis_arg(spec)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    k = spec.k
    if fractions is None:
        m = -(-flat.size // k)
        sizes = (m,) * k
        padded = jnp.pad(flat, (0, m * k - flat.size))
        chunks = padded if k == 1 else padded.reshape(k, m)
    else:
        sizes = chunk_sizes(flat.size, fractions)
        m = max(sizes)
        rows, off = [], 0
        for s in sizes:
            c = flat[off:off + s]
            off += s
            rows.append(c if s == m else jnp.pad(c, (0, m - s)))
        chunks = rows[0] if k == 1 else jnp.stack(rows)

    idx = jax.lax.axis_index(axis)
    rows_iota = jnp.arange(k)

    # reduce: arrivals accumulate into their tree's row.  k=1 and
    # single-row waves need no masking at all (ppermute zero-fills
    # devices nobody sent to); multi-row waves scatter the arrival to a
    # one-hot (k, m) contribution first.
    for w, rnd in enumerate(spec.reduce_rounds):
        with jax.named_scope(f"edst/t*/w{w}/reduce"):
            recv, flag, recv_rows = _fused_send(chunks, rnd, idx, axis,
                                                r_wire)
            if k == 1:
                chunks = _acc(chunks, recv)
            elif len(recv_rows) == 1:
                r0 = int(recv_rows[0])
                chunks = chunks.at[r0].set(_acc(chunks[r0], recv))
            else:
                row = jnp.asarray(rnd.recv_row)[idx]
                masked = jnp.where(flag, recv, jnp.zeros_like(recv))
                contrib = (rows_iota == row).astype(chunks.dtype)[:, None] \
                    * masked[None, :]
                chunks = _acc(chunks.reshape(-1),
                              contrib.reshape(-1)).reshape(k, m)

    # broadcast: arrivals overwrite their tree's row on destinations.
    # Quantized, the per-row totals are packed ONCE here into the
    # f32-lane wire and forwarded verbatim down the levels (codec cost
    # amortized over depth hops, one quantization error instead of one
    # per hop, and 4x fewer elements under every wave's row machinery).
    q_bcast = codec != "off" and bool(spec.bcast_rounds) and dtype in _FLOATS
    if q_bcast:
        chunks = _pack_wire32(chunks)
    base = len(spec.reduce_rounds)
    for w, rnd in enumerate(spec.bcast_rounds):
        with jax.named_scope(f"edst/t*/w{base + w}/bcast"):
            recv, flag, recv_rows = _fused_send(chunks, rnd, idx, axis)
            if k == 1:
                chunks = jnp.where(flag, recv, chunks)
            elif len(recv_rows) == 1:
                r0 = int(recv_rows[0])
                chunks = chunks.at[r0].set(jnp.where(flag, recv,
                                                     chunks[r0]))
            else:
                row = jnp.asarray(rnd.recv_row)[idx]
                sel = ((rows_iota == row) & flag)[:, None]
                chunks = jnp.where(sel, recv[None, :], chunks)
    if q_bcast:
        chunks = _unpack_wire32(chunks, dtype, m)

    if k == 1:
        out = chunks[:flat.size] if fractions is None else chunks[:sizes[0]]
    elif fractions is None:
        out = chunks.reshape(-1)[:flat.size]
    else:
        parts = [chunks[j, :s] for j, s in enumerate(sizes) if s > 0]
        out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# pipelined segmented execution (inside shard_map) -- the default engine
# ---------------------------------------------------------------------------

def auto_segments(spec: PipelinedAllreduceSpec, row_elems: int,
                  itemsize: int = 4) -> int:
    """The segment count the backend-calibrated cost model picks for
    ``row_elems``-element chunk rows (see ``CostModel.for_backend``)."""
    cm = CostModel.for_backend(jax.default_backend())
    nbytes = row_elems * itemsize * max(1, spec.k)
    return max(1, min(cm.best_segments(nbytes, spec), row_elems or 1))


def _gather(table, idx):
    return jnp.asarray(table)[idx]


def _select_payload(rows, wv, idx):
    """The wave's outgoing chunk: most waves ship one row statically;
    multi-row waves select per device via the spec's send-row table."""
    payload = rows[wv.rows[0]]
    for r in wv.rows[1:]:
        payload = jnp.where(_gather(wv.send_row == r, idx), rows[r], payload)
    return payload


def _apply_wave(rows, wv, recv, idx):
    """Land one wave's arrival: accumulate into reduce destinations,
    overwrite broadcast destinations, leave everyone else untouched.
    ``wv.sole_add`` waves skip masking (zero payload on non-destinations).
    Rows the wave does not touch may be ``None``."""
    zero = jnp.zeros((), recv.dtype)
    for j in range(len(rows)):
        rf, bf = wv.reduce_flag[j], wv.bcast_flag[j]
        if not (rf.any() or bf.any()):
            continue
        if wv.sole_add == j:
            rows[j] = _acc(rows[j], recv)
            continue
        base = rows[j]
        if rf.any():
            base = _acc(base, jnp.where(_gather(rf, idx), recv, zero))
        if bf.any():
            base = jnp.where(_gather(bf, idx), recv, base)
        rows[j] = base
    return rows


def _rows_of(flat, k, sizes, mrow):
    rows, off = [], 0
    for s in sizes:
        c = flat[off:off + s]   # the last row may run short of its size
        off += s
        rows.append(c if c.shape[0] == mrow
                    else jnp.pad(c, (0, mrow - c.shape[0])))
    return rows


def _rows_out(rows, sizes, size):
    """Row widths may exceed the logical stripe sizes (segment padding),
    so each row is cut back to its stripe before reassembly."""
    parts = [rows[j][:s] for j, s in enumerate(sizes) if s > 0]
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return out[:size]


def pipelined_tree_allreduce(x, spec: PipelinedAllreduceSpec,
                             quantize: bool = False, segments="auto",
                             fractions=None, codec=None):
    """Allreduce (sum) of the per-device array ``x`` over ``spec.axes``
    with the pipelined segmented wave program (the default engine).

    Must run inside a ``shard_map`` whose manual axes include
    ``spec.axes``.  ``x`` is flattened and striped into k chunk rows
    (uniform, or weighted by ``fractions`` via ``chunk_sizes``), padded
    to a common row width.  ``segments`` splits each row into S pipeline
    segments: S=1 unrolls the wave list directly (no pipelining
    overhead); S>1 runs a ``fori_loop`` over ``waves + S - 1`` steps in
    which wave w moves segment ``t - w`` -- steady state keeps every
    tree edge busy and the HLO holds each wave's collective exactly
    once, whatever S is; its segments are whole tiles of a
    segment-major carry (see :func:`_scanned`).  ``"auto"`` asks the
    backend-calibrated cost model (:func:`auto_segments`).
    ``quantize``/``codec`` select the int8 wire (see module docstring).
    """
    if spec.k == 0 or x.size == 0:
        return x
    if fractions is not None and len(fractions) != spec.k:
        raise ValueError(f"{len(fractions)} fractions for k={spec.k} trees; "
                         "spec and striping must come from the same schedule")
    codec = resolve_codec(codec) if quantize else "off"
    if x.dtype not in _FLOATS:
        codec = "off"       # integer payloads always travel verbatim
    if codec == "off":
        quantize = False    # model-disabled codec: identical f32 program
    axis = _axis_arg(spec)
    idx = jax.lax.axis_index(axis)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    k = spec.k
    if fractions is None:
        mrow = -(-flat.size // k)
        sizes = (mrow,) * k
    else:
        sizes = chunk_sizes(flat.size, fractions)
        mrow = max(sizes)
    if segments == "auto" or segments is None:
        segments = auto_segments(spec, mrow, dtype.itemsize)
    segments = max(1, min(int(segments), mrow))
    _note_trace("pipelined", spec, x, codec=codec if quantize else None,
                fractions=fractions, segments=segments)

    if segments == 1:
        rows = _rows_of(flat, k, sizes, mrow)
        if quantize:
            rows = _q8_unrolled(rows, spec, idx, axis, codec)
        else:
            for w, wv in enumerate(spec.waves):
                with jax.named_scope(_wave_label(w, wv)):
                    recv = jax.lax.ppermute(_select_payload(rows, wv, idx),
                                            axis, list(wv.perm))
                    rows = _apply_wave(rows, wv, recv, idx)
    else:
        msub = _whole_tiles(-(-mrow // segments), dtype.itemsize)
        rows = _scanned(_rows_of(flat, k, sizes, (segments + 1) * msub),
                        spec, idx, axis, segments, msub,
                        codec if quantize else None)

    out = _rows_out(rows, sizes, flat.size)
    return out.reshape(shape).astype(dtype)


def _q8_unrolled(rows, spec, idx, axis, codec):
    """S=1 quantized program: phase-separated waves; reduce hops' wire
    per the codec policy, then every row packs ONCE at the reduce/
    broadcast boundary and the int8 wire forwards verbatim down the
    trees."""
    dtype = rows[0].dtype
    r_wire = _REDUCE_WIRE[codec]
    bnd = spec.q8_boundary
    for w, wv in enumerate(spec.q8_waves[:bnd]):
        with jax.named_scope(_wave_label(w, wv)):
            payload = _select_payload(rows, wv, idx)
            if r_wire == "q8" and payload.dtype in _FLOATS:
                wire = jax.lax.ppermute(q8_pack(payload), axis,
                                        list(wv.perm))
                if wv.sole_add >= 0:
                    rows[wv.sole_add] = q8_combine(wire, rows[wv.sole_add])
                    continue
                recv = q8_unpack(wire, dtype)
            else:
                recv = _send(payload, axis, wv.perm, r_wire)
            rows = _apply_wave(rows, wv, recv, idx)
    if bnd == len(spec.q8_waves) or dtype not in _FLOATS:
        for w, wv in enumerate(spec.q8_waves[bnd:]):
            with jax.named_scope(_wave_label(bnd + w, wv)):
                recv = jax.lax.ppermute(_select_payload(rows, wv, idx),
                                        axis, list(wv.perm))
                rows = _apply_wave(rows, wv, recv, idx)
        return rows
    mrow = rows[0].shape[0]
    if len(rows) == 1:
        packed = [_pack_wire32(rows[0])]
    else:
        packed = list(_pack_wire32(jnp.stack(rows)))
    for w, wv in enumerate(spec.q8_waves[bnd:]):
        with jax.named_scope(_wave_label(bnd + w, wv)):
            recv = jax.lax.ppermute(_select_payload(packed, wv, idx),
                                    axis, list(wv.perm))
            for j in range(len(packed)):
                if wv.bcast_flag[j].any():
                    packed[j] = jnp.where(_gather(wv.bcast_flag[j], idx),
                                          recv, packed[j])
    if len(packed) == 1:
        return [_unpack_wire32(packed[0], dtype, mrow)]
    return list(_unpack_wire32(jnp.stack(packed), dtype, mrow))


def _whole_tiles(n: int, itemsize: int) -> int:
    """``n`` elements rounded up to whole (8, 128) tiles of 32-bit words
    (the (16, 128) bf16 and (32, 128) int8 tiles hold the same bytes)."""
    quantum = _TILE_BYTES // itemsize
    return -(-n // quantum) * quantum


def _scanned(rows, spec, idx, axis, segments, msub, codec):
    """S>1: software-pipeline the wave program with a ``fori_loop`` over
    the step index; the body issues every wave once on segment
    ``t - stage(w)``, so the compiled HLO holds one collective per wave
    however many segments stream through.

    The carry is segment-major: ``(k, S + 1, msub // 128, 128)``, where
    ``msub`` is a whole number of tiles, so one segment is a run of whole
    (8, 128) tiles that a read or write moves without touching its
    neighbours, and a ``(S + 1) * msub`` chunk row reshapes into it as
    is.  Segment S is a spare: a wave whose segment lies outside
    ``[0, S)`` (pipeline fill and drain) reads and writes the spare
    instead, and no output reads the spare, so no arrival needs
    masking.  The int8 carry of the quantized scan has the same layout,
    each row holding the ``(msub + 4,)`` wire in whole int8 tiles.

    Each step reads every wave's segment (and the pack stage's) from the
    carry it received, then sends and lands every wave, then writes the
    segments back.  That is exact: wave w touches only segment
    ``t - stage(w)``, the stages are distinct, and the pack stage reads
    ``st`` at ``t - boundary``, which no reduce wave writes in the same
    step; the spare absorbs every out-of-range access.  With no data
    path from one wave's write to another's read, the waves' collectives
    are free to run at once on their disjoint links."""
    k = len(rows)
    dtype = rows[0].dtype
    tile = (msub // _LANES, _LANES)
    st = jnp.stack(rows).reshape(k, segments + 1, *tile)
    waves = spec.waves if codec is None else spec.q8_waves
    boundary = len(waves) if codec is None else spec.q8_boundary
    # quantized scans insert a pack pseudo-stage at the phase boundary,
    # shifting broadcast waves one step later
    stage = [w if (codec is None or w < boundary) else w + 1
             for w in range(len(waves))]
    nsteps = (len(waves) if codec is None else len(waves) + 1) + segments - 1
    bcast = [codec is not None and w >= boundary for w in range(len(waves))]
    used = [sorted(set(wv.rows) | {j for j in range(k)
                                   if wv.reduce_flag[j].any()
                                   or wv.bcast_flag[j].any()})
            for wv in waves]
    pst = None
    if codec is not None:
        wire = _whole_tiles(msub + 4, 1)
        # the packed carry varies over the manual axes as ``st`` does
        pst = jnp.zeros((k, segments + 1, wire // _LANES, _LANES), jnp.int8)
        vma = tuple(jax.typeof(st).vma)
        if vma:
            pst = jax.lax.pcast(pst, vma, to="varying")

    def seg_read(arr, j, seg, n):
        flat = jax.lax.dynamic_slice(
            arr, (j, seg, 0, 0), (1, 1) + arr.shape[2:]).reshape(-1)
        return flat if n == flat.shape[0] else flat[:n]

    def seg_write(arr, j, seg, val):
        pad = arr.shape[2] * arr.shape[3] - val.shape[0]
        if pad:
            val = jnp.pad(val, (0, pad))
        return jax.lax.dynamic_update_slice(
            arr, val.reshape((1, 1) + arr.shape[2:]), (j, seg, 0, 0))

    def body(t, carry):
        st, pst = carry

        def at(s):      # segment t - s, or the spare (S) outside [0, S)
            seg = t - s
            return jnp.where((seg >= 0) & (seg < segments), seg, segments)

        segs = [at(s) for s in stage]
        cur = []
        for w, wv in enumerate(waves):
            with jax.named_scope(_wave_label(w, wv)):
                src, n = (pst, msub + 4) if bcast[w] else (st, msub)
                cur.append([seg_read(src, j, segs[w], n) if j in used[w]
                            else None for j in range(k)])
        packin = None
        if codec is not None:
            # pack pseudo-stage: segment t - boundary crosses into bcast
            pseg = at(boundary)
            packin = [seg_read(st, j, pseg, msub) for j in range(k)]
        # every read completes before the first write lands in the carry
        cur, packin, st, pst = jax.lax.optimization_barrier(
            (cur, packin, st, pst))
        new = []
        for w, wv in enumerate(waves):
            with jax.named_scope(_wave_label(w, wv)):
                recv = _send(_select_payload(cur[w], wv, idx), axis,
                             wv.perm, None if bcast[w]
                             else _REDUCE_WIRE.get(codec))
                new.append(_apply_wave(list(cur[w]), wv, recv, idx))
        for w, wv in enumerate(waves):
            with jax.named_scope(_wave_label(w, wv)):
                for j in used[w]:
                    if new[w][j] is cur[w][j]:
                        continue
                    if bcast[w]:
                        pst = seg_write(pst, j, segs[w], new[w][j])
                    else:
                        st = seg_write(st, j, segs[w], new[w][j])
        if codec is not None:
            for j in range(k):
                pst = seg_write(pst, j, pseg, q8_pack(packin[j]))
        return st, pst

    st, pst = jax.lax.fori_loop(0, nsteps, body, (st, pst))
    if codec is not None:
        # decode in the carry's tiled form: the payload is the first
        # msub // 128 rows of each segment, the scale the next row's head
        r = tile[0]
        scales = jax.lax.bitcast_convert_type(pst[:, :, r, :4], jnp.float32)
        st = (pst[:, :, :r].astype(jnp.float32)
              * scales[..., None, None]).astype(dtype)
    return list(st.reshape(k, -1))


def tree_allreduce(x, spec, quantize: bool = False, segments="auto"):
    """Allreduce (sum) of the per-device array ``x`` over ``spec.axes``.

    Dispatches on the spec form: a
    :class:`repro.core.collectives.PipelinedAllreduceSpec` runs the
    pipelined segmented engine (the default the rest of the stack
    compiles), a :class:`repro.core.collectives.StripedCollectiveSpec`
    the striped reduce-scatter/allgather engine
    (:mod:`repro.dist.striped`; stripe windows replace segment streaming,
    so ``segments`` does not apply), a
    :class:`repro.core.collectives.FusedAllreduceSpec` the fused
    global-round baseline, a :class:`TreeAllreduceSpec` the per-tree
    chains.  All return the summed array in the original shape
    (replicated across the fabric).
    """
    if isinstance(spec, PipelinedAllreduceSpec):
        return pipelined_tree_allreduce(x, spec, quantize, segments)
    if isinstance(spec, StripedCollectiveSpec):
        from .striped import striped_allreduce  # late: striped imports us
        return striped_allreduce(x, spec, quantize=quantize)
    if isinstance(spec, FusedAllreduceSpec):
        return fused_tree_allreduce(x, spec, quantize)
    return per_tree_allreduce(x, spec, quantize)