"""The tree-combine Pallas kernel (``repro.kernels.tree_combine``):
``out = partial + sum(recv, axis=0)`` over a flat buffer.  On the chip
each call is one ``tpu_custom_call`` instruction that XLA names after
the kernel's jitted function (``tree_combine.16``).  The trace's event
for a call carries the instruction's HLO text: its operand and result
shapes, with their memory spaces."""
from __future__ import annotations

import re
import sys

import numpy as np

from trace_reduce import nbytes

NAME = re.compile(r"^tree_combine(\.\d+)?$")


def is_call(op) -> bool:
    return op.opcode == "custom-call" and bool(NAME.match(op.name))


def call_bytes(op) -> float:
    """Bytes one call reads and writes: its operands and its result.  In
    the EDST cell all three sit in on-chip memory (space 1), so no HBM
    roofline bounds a call."""
    return nbytes(op.operand_arrays() + op.result_arrays())


def result_elems(op) -> float:
    return sum(float(np.prod(shape, dtype=np.float64))
               for _, shape, _ in op.result_arrays())


def accounts_for_sync(ctx) -> bool:
    """Whether the calls that :func:`is_call` finds in the trace write at
    least what the all-reduce of the gradient has to combine, so that
    calls under a name it fails to match cannot go unseen.  In a tree
    all-reduce every chip but the root has its subtree's sum added into
    its parent's partial once, so the combines write (chips - 1) times
    the gradient a step, summed over the chips; the gradient holds at
    least the family's matrix weights (``flops/<family>.py``)."""
    rec = ctx.trace
    written = sum(rec.per_step(d, is_call, result_elems) for d in rec.devices)
    need = (len(rec.devices) - 1) * ctx.cell.flops.matmul_weights(
        ctx.program.sizes)
    if written < need:
        print(f"tree_combine: the matched calls write {written:.0f} elements "
              f"a step over the chips, under the {need} that the gradient's "
              "all-reduce combines; kernel metrics left out", file=sys.stderr)
    return written >= need


def slowest(ctx):
    """``(chip, ns)``: the chip where the calls take longest and that
    time inside the window; None where no call ran, or where the calls
    found fall short of the sync's volume (:func:`accounts_for_sync`)."""
    rec = ctx.trace
    times = [rec.busy_ns(d, is_call) for d in rec.devices]
    if not rec.steps or not max(times, default=0) \
            or not accounts_for_sync(ctx):
        return None
    return rec.devices[times.index(max(times))], max(times)
