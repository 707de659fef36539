"""Reduce a JAX profiler trace (``.xplane.pb``) to the record that the
per-layer metric files read.

What a TPU trace holds, as read by hand on a TPU v5 lite: one plane per
chip (``/device:TPU:<n>``) whose ``XLA Ops`` line has one event per
executed HLO instruction, named by the instruction's HLO text
(``%tree_combine.1 = f32[1048576]{0} custom-call(f32[1,1048576]{1,0}
..., f32[1048576]{0} %b.1)``), so the result and operand shapes are in
the name.  A ``while`` appears as one event that spans its body's
events, which appear too.  Asynchronous copies sit on a line of their
own (``Async XLA Ops``) and are not counted as busy time.  The host
plane (``/host:CPU``) holds the benchmark's ``TraceAnnotation`` spans on
the Python thread, on the same clock.

The op name of the ``jax.named_scope`` an instruction was traced under
(``edst/t0/w1/reduce``) is not in the trace; it is in the instruction's
``metadata={op_name=...}`` in the compiled module's HLO text, which the
caller passes in.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# benchmark host spans: the whole step and its parts
SPAN_PREFIX = "bench/"
STEP_SPAN = "bench/step"
# instructions whose event spans the events of their own body
CONTAINERS = frozenset({"while", "conditional", "call"})

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


@dataclass(frozen=True)
class Op:
    """One HLO instruction as the trace names it."""
    name: str          # instruction name, e.g. "tree_combine.1"
    opcode: str        # e.g. "custom-call", "fusion", "while"
    result: str        # HLO text of the result shape(s)
    operands: str      # HLO text of the operand list
    op_name: str = ""  # metadata op_name (named scopes), "" if unknown

    def result_arrays(self):
        return parse_arrays(self.result)

    def operand_arrays(self):
        return parse_arrays(self.operands)


@dataclass
class DeviceOps:
    """The executed instructions of one chip: intervals in ns and an
    index into :attr:`TraceRecord.ops` per event."""
    device: int
    start: np.ndarray
    end: np.ndarray
    op: np.ndarray


@dataclass
class TraceRecord:
    window: tuple            # (t0, t1) ns: first step span start, last end
    steps: int               # benchmark step spans inside the window
    host_spans: dict         # span name -> (n, 2) array of [start, end] ns
    devices: list            # DeviceOps, one per chip
    ops: list                # Op table

    @property
    def window_ns(self) -> int:
        return int(self.window[1] - self.window[0])

    def mask(self, dev: DeviceOps, pred) -> np.ndarray:
        """Events of ``dev`` whose op satisfies ``pred`` (containers are
        never selected: their bodies are)."""
        table = np.fromiter((pred(o) and o.opcode not in CONTAINERS
                             for o in self.ops), bool, len(self.ops))
        return table[dev.op] if len(dev.op) else np.zeros(0, bool)

    def busy_ns(self, dev: DeviceOps, pred=None) -> int:
        """Length of the union of ``dev``'s op intervals (those selected by
        ``pred``, default all) inside the window."""
        sel = self.mask(dev, pred or _any)
        return union_length(*clip(dev.start[sel], dev.end[sel], self.window))

    def per_step(self, dev: DeviceOps, pred, weight) -> float:
        """``weight(op)`` summed over ``dev``'s events of the ops that
        ``pred`` selects and that overlap the window, over the steps."""
        inside = (dev.end > self.window[0]) & (dev.start < self.window[1])
        idx = dev.op[self.mask(dev, pred) & inside]
        counts = np.bincount(idx, minlength=len(self.ops))
        return sum(float(n) * weight(self.ops[k])
                   for k, n in enumerate(counts) if n) / self.steps

    def exposed_ns(self, dev: DeviceOps, pred) -> int:
        """Length of the part of the union of ``pred``'s intervals during
        which no other op runs on ``dev``."""
        sel = self.mask(dev, pred)
        others = self.mask(dev, _any) & ~sel
        a = merge(*clip(dev.start[sel], dev.end[sel], self.window))
        b = merge(*clip(dev.start[others], dev.end[others], self.window))
        return union_length(*a) - intersection_length(a, b)

    def busiest(self) -> DeviceOps:
        return max(self.devices, key=self.busy_ns)


def _any(op: Op) -> bool:
    return True


def clip(start, end, window):
    s = np.clip(start, window[0], window[1])
    e = np.clip(end, window[0], window[1])
    keep = e > s
    return s[keep], e[keep]


def merge(start, end):
    """Sorted, disjoint intervals covering the same points."""
    if len(start) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    run_end = np.maximum.accumulate(e)
    new = np.empty(len(s), bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(idx[1:] - 1, len(s) - 1)
    return s[idx], run_end[ends]


def union_length(start, end) -> int:
    s, e = merge(start, end)
    return int(np.sum(e - s))


def intersection_length(a, b) -> int:
    """Overlap of two merged interval lists."""
    (sa, ea), (sb, eb) = a, b
    total, i, j = 0, 0, 0
    while i < len(sa) and j < len(sb):
        lo, hi = max(sa[i], sb[j]), min(ea[i], eb[j])
        if hi > lo:
            total += int(hi - lo)
        if ea[i] < eb[j]:
            i += 1
        else:
            j += 1
    return total


def parse_arrays(text: str):
    """``[(dtype, shape, memory space), ...]`` of every array type in HLO
    text.  The space is the layout's ``S(n)`` (0, HBM, where absent; the
    compiler puts arrays it keeps on chip in space 1)."""
    out = []
    for dt, dims, layout in _ARRAY.findall(text):
        if dt in _DTYPE_BYTES:
            space = _SPACE.search(layout or "")
            out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                        int(space.group(1)) if space else 0))
    return out


def nbytes(arrays, space=None) -> float:
    """Bytes of ``arrays``; with ``space``, only those in that memory
    space."""
    total = 0.0
    for dt, shape, where in arrays:
        if space is None or where == space:
            total += _DTYPE_BYTES[dt] * float(np.prod(shape,
                                                      dtype=np.float64))
    return total


def parse_op(text: str) -> Op:
    """Split an event's HLO text into an :class:`Op` (without op_name)."""
    name, _, rest = text.partition("=")
    name = name.strip().lstrip("%")
    rest = rest.strip()
    depth, i = 0, 0
    while i < len(rest):
        c = rest[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    result, tail = rest[:i], rest[i:].strip()
    opcode, _, args = tail.partition("(")
    depth, j = 1, 0
    while j < len(args) and depth:
        depth += {"(": 1, ")": -1}.get(args[j], 0)
        j += 1
    return Op(name, opcode.strip(), result, args[:j - 1] if depth == 0
              else args)


def hlo_op_names(hlo_text: str) -> dict:
    """instruction name -> metadata op_name, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_DEF.match(line)
        if not m:
            continue
        o = _OP_NAME.search(line)
        if o:
            out[m.group(1)] = o.group(1)
    return out


def reduce_trace(xplane_path: str, hlo_texts=()) -> TraceRecord:
    """Read ``xplane_path`` into a :class:`TraceRecord`.  ``hlo_texts``
    are the compiled modules' HLO texts that give op names."""
    from jax.profiler import ProfileData
    names = {}
    for text in hlo_texts:
        names.update(hlo_op_names(text))
    pd = ProfileData.from_file(xplane_path)
    ops, op_index = [], {}
    devices, spans = [], {}
    for plane in pd.planes:
        dm = DEVICE_PLANE.match(plane.name)
        if dm:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                start, end, idx = [], [], []
                for ev in line.events:
                    k = op_index.get(ev.name)
                    if k is None:
                        op = parse_op(ev.name)
                        op = Op(op.name, op.opcode, op.result, op.operands,
                                names.get(op.name, ""))
                        k = op_index[ev.name] = len(ops)
                        ops.append(op)
                    s = int(ev.start_ns)
                    start.append(s)
                    end.append(s + int(ev.duration_ns))
                    idx.append(k)
                devices.append(DeviceOps(int(dm.group(1)),
                                         np.asarray(start, np.int64),
                                         np.asarray(end, np.int64),
                                         np.asarray(idx, np.int64)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.setdefault(ev.name, []).append(
                            (s, s + int(ev.duration_ns)))
    spans = {k: np.asarray(sorted(v), np.int64) for k, v in spans.items()}
    steps = spans.get(STEP_SPAN, np.zeros((0, 2), np.int64))
    if not len(steps) or not devices:
        raise ValueError(f"{xplane_path}: no {STEP_SPAN} spans or no TPU "
                         "device plane in the trace")
    devices.sort(key=lambda d: d.device)
    window = (int(steps[:, 0].min()), int(steps[:, 1].max()))
    return TraceRecord(window, len(steps), spans, devices, ops)


def idle_gaps(rec: TraceRecord, dev: DeviceOps, top: int = 10):
    """The ``top`` longest gaps between ``dev``'s ops inside the window,
    each labelled by the innermost benchmark host span open at its
    midpoint: ``[(label, seconds), ...]``."""
    s, e = merge(*clip(dev.start, dev.end, rec.window))
    lo = np.concatenate([[rec.window[0]], e])
    hi = np.concatenate([s, [rec.window[1]]])
    gaps = sorted(((int(b - a), int(a), int(b)) for a, b in zip(lo, hi)
                   if b > a), reverse=True)[:top]
    out = []
    for length, a, b in gaps:
        mid, label, best = (a + b) // 2, "no span", None
        for name, iv in rec.host_spans.items():
            inside = iv[(iv[:, 0] <= mid) & (iv[:, 1] >= mid)]
            for st, en in inside:
                if best is None or en - st < best:
                    best, label = en - st, name
        out.append([label, length / 1e9])
    return out


def top_ops(rec: TraceRecord, dev: DeviceOps, top: int = 10):
    """The ``top`` instructions by summed device time on ``dev`` inside
    the window, containers left out: ``[(label, seconds), ...]``."""
    s, e = clip(dev.start, dev.end, rec.window)
    keep = (np.minimum(dev.end, rec.window[1])
            > np.maximum(dev.start, rec.window[0]))
    idx = dev.op[keep]
    totals = np.bincount(idx, weights=(e - s).astype(np.float64),
                         minlength=len(rec.ops))
    order = np.argsort(-totals)
    out = []
    for k in order:
        op = rec.ops[k]
        if totals[k] <= 0 or len(out) == top:
            break
        if op.opcode in CONTAINERS:
            continue
        scope = op.op_name.split("/")
        label = f"{op.name} {op.opcode} {'/'.join(scope[-3:])}".strip()
        out.append([label[:160], totals[k] / 1e9])
    return out
