"""Which layer of the train step an operation belongs to, from the named
scopes it was traced under (its HLO ``op_name``).

The program scopes each phase of the step (``dist/steps.py``) and each
sublayer of the model (``models/layers.py``); JAX wraps the scope of a
differentiated function in ``jvp(...)`` for the forward pass and in
``transpose(jvp(...))`` for the backward pass, and names recomputed ops
``rematted_computation``.  An op's phase is the first rule that matches:

1. ``sync``: under ``step/sync`` or an EDST wave scope ``edst/``;
2. ``optimizer``: under ``step/optimizer``;
3. ``backward``: ``transpose(`` or ``rematted_computation``;
4. ``forward``: under ``step/model`` or a ``model/`` sublayer;
5. ``unattributed``: anything else, a scope lost on the way to the
   compiled program.

Ops the compiler makes itself carry no ``op_name`` at all: copies
between memory spaces, hoisted converts, the loops it builds for a large
reshape.  In the chip trace each runs inside one phase: such an event
takes the phase of the named events just before and just after it on
its chip where the two agree, and is unattributed where they do not.
"""
from __future__ import annotations

import re

import numpy as np

import trace_reduce

PHASES = ("sync", "optimizer", "backward", "forward", "unattributed")

# ``model/<name>``, but not the phase scope ``step/model``
_SUBLAYER = re.compile(r"(?<!step/)\bmodel/(\w+)")


def phase(op_name: str) -> str:
    """The phase of an op traced under ``op_name``."""
    if "step/sync" in op_name or "edst/" in op_name:
        return "sync"
    if "step/optimizer" in op_name:
        return "optimizer"
    if "transpose(" in op_name or "rematted_computation" in op_name:
        return "backward"
    if "step/model" in op_name or sublayer(op_name):
        return "forward"
    return "unattributed"


def sublayer(op_name: str) -> str | None:
    """The model sublayer (``model/<name>``) of an op, or None."""
    m = _SUBLAYER.search(op_name)
    return m.group(1) if m else None


def is_remat(op_name: str) -> bool:
    """An op the backward pass recomputes."""
    return "rematted_computation" in op_name


def event_phases(rec, dev) -> np.ndarray:
    """The index into :data:`PHASES` of each of ``dev``'s events; -1 for
    container events (``while`` and the like), whose bodies are counted."""
    ops = rec.ops
    table = np.array([PHASES.index(phase(o.op_name)) for o in ops], int)
    named = np.array([bool(o.op_name) for o in ops], bool)
    container = np.array([o.opcode in trace_reduce.CONTAINERS for o in ops],
                         bool)
    out = table[dev.op] if len(dev.op) else np.zeros(0, int)
    if not len(out):
        return out
    own = ~container[dev.op]
    order = np.argsort(dev.start, kind="stable")
    order = order[own[order]]
    ph = out[order]
    has = named[dev.op[order]]
    n = len(order)
    idx = np.arange(n)
    # for an unnamed event: the last named event before it and the first
    # after it, in start order
    before = np.maximum.accumulate(np.where(has, idx, -1))
    after = np.minimum.accumulate(np.where(has, idx, n)[::-1])[::-1]
    prev_ph = np.where(before >= 0, ph[np.maximum(before, 0)], -2)
    next_ph = np.where(after < n, ph[np.minimum(after, n - 1)], -3)
    ph = np.where(~has & (prev_ph == next_ph), prev_ph, ph)
    out[order] = ph
    out[container[dev.op]] = -1
    return out


def phase_ns(rec, dev, name: str) -> int | None:
    """Length of the union, inside the window, of ``dev``'s events of
    phase ``name``; None where it has no such event."""
    sel = event_phases(rec, dev) == PHASES.index(name)
    if not sel.any():
        return None
    s, e = trace_reduce.clip(dev.start[sel], dev.end[sel], rec.window)
    return trace_reduce.union_length(s, e)


def read_phase_ms(ctx, name: str):
    """Device time per step of phase ``name`` on the busiest chip (the
    chip ``step.device_ms`` reads), in ms; None where no event there is
    of that phase."""
    rec = ctx.trace
    if not rec.steps:
        return None
    ns = phase_ns(rec, rec.busiest(), name)
    return None if ns is None else ns / rec.steps / 1e6


def in_sublayer(name: str):
    """A predicate on trace ops: those of sublayer ``name``."""
    return lambda op: sublayer(op.op_name) == name


def read_ms(ctx, pred):
    """Device time per step of the ops ``pred`` selects, as the union of
    their intervals inside the window on the busiest chip, in ms; None
    where no op there is selected."""
    rec = ctx.trace
    if not rec.steps:
        return None
    dev = rec.busiest()
    if not rec.mask(dev, pred).any():
        return None
    return rec.busy_ns(dev, pred) / rec.steps / 1e6
