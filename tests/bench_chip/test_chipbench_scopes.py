"""The scope classifier (``scopes.py``) and the per-layer readers built on
it: each op_name form the compiled train step carries, a synthetic trace
read by hand, and the recorded trace, where most scopes are absent."""
import types

import numpy as np
import pytest

from chipbench_paths import BENCH, DATA

import chip_harness
import scopes
import trace_reduce as tr


@pytest.mark.parametrize("op_name, phase, sublayer", [
    ("jit(step)/jvp(step/model)/while/body/closed_call/model/attention/"
     "dot_general", "forward", "attention"),
    ("jit(step)/transpose(jvp(step/model))/while/body/closed_call/model/mlp/"
     "dot_general", "backward", "mlp"),
    ("jit(step)/transpose(jvp(step/model))/while/body/closed_call/"
     "checkpoint/rematted_computation/model/attention/exp",
     "backward", "attention"),
    ("checkpoint/rematted_computation/reduce_sum", "backward", None),
    ("jit(step)/jvp(step/model)/model/head/while/body/reduce_max",
     "forward", "head"),
    ("jit(step)/transpose(jvp(step/model))/model/embed/scatter-add",
     "backward", "embed"),
    ("jit(step)/model/attention/cos", "forward", "attention"),
    ("jit(step)/jvp(step/model)/rsqrt", "forward", None),
    ("jit(step)/step/optimizer/mul", "optimizer", None),
    ("jit(step)/shard_map/step/sync/edst/t0/w1/reduce/add", "sync", None),
    ("jit(step)/shard_map/step/sync/concatenate", "sync", None),
    ("jit(step)/edst/t0/w1/reduce/tree_combine", "sync", None),
    ("jit(step)/shard_map/psum", "unattributed", None),
    ("checkpoint/reduce_sum", "unattributed", None),
    ("", "unattributed", None),
    ("jit(step)/step/model/model/mlp/add", "forward", "mlp"),
])
def test_classifier(op_name, phase, sublayer):
    assert scopes.phase(op_name) == phase
    assert scopes.sublayer(op_name) == sublayer
    assert phase in scopes.PHASES


# one step of a synthetic trace, repeated twice 1000 ns apart:
# (op_name, opcode, start, end) in ns
STEP = [
    ("jit(step)/jvp(step/model)/while", "while", 0, 700),
    ("jit(step)/jvp(step/model)/while/body/closed_call/model/attention/"
     "dot_general", "fusion", 0, 100),
    ("jit(step)/transpose(jvp(step/model))/while/body/closed_call/"
     "model/attention/dot_general", "fusion", 100, 300),
    ("jit(step)/transpose(jvp(step/model))/while/body/closed_call/"
     "checkpoint/rematted_computation/model/mlp/dot_general", "fusion",
     150, 250),
    ("jit(step)/step/optimizer/mul", "fusion", 300, 350),
    ("jit(step)/shard_map/step/sync/edst/t0/w0/reduce/add", "fusion",
     350, 500),
    ("jit(step)/shard_map/step/sync/concatenate", "fusion", 480, 520),
    ("", "copy", 520, 540),
    ("jit(step)/jvp(step/model)/model/head/while/body/dot_general", "fusion",
     540, 600),
    ("jit(step)/transpose(jvp(step/model))/model/embed/scatter-add",
     "fusion", 600, 650),
]
# by hand, per step, in ns
WANT = {"step.forward_ms": 100 + 60, "step.backward_ms": 200 + 50,
        "step.remat_ms": 100, "step.optimizer_ms": 50,
        "step.sync_ms": 170, "step.unattributed_ms": 20,
        "model.attention_ms": 300, "model.mlp_ms": 100,
        "model.head_ms": 60, "model.embed_ms": 50}
PHASE_METRICS = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
                 "step.sync_ms", "step.unattributed_ms")


def _record(step=STEP, chips=1):
    ops = [tr.Op(f"op.{k}", opcode, "f32[8]", "", name)
           for k, (name, opcode, _, _) in enumerate(step)]
    devices = []
    for d in range(chips):
        start = [s + 1000 * i for i in range(2) for _, _, s, _ in step]
        end = [e + 1000 * i for i in range(2) for _, _, _, e in step]
        idx = [k for _ in range(2) for k in range(len(step))]
        devices.append(tr.DeviceOps(d, np.asarray(start, np.int64),
                                    np.asarray(end, np.int64),
                                    np.asarray(idx, np.int64)))
    spans = {"bench/step": np.asarray([[0, 1000], [1000, 2000]], np.int64)}
    return tr.TraceRecord((0, 2000), 2, spans, devices, ops)


def _ctx(rec, cell="smollm-135m.train.edst.4chip"):
    return chip_harness.MetricContext(
        rec, chip_harness.load_cell(cell), types.SimpleNamespace(sizes={}),
        1000.0, None)


def _metric(name):
    return chip_harness.load_module(BENCH / "metrics" / f"{name}.py")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_by_hand(name):
    assert _metric(name).read(_ctx(_record())) == pytest.approx(
        WANT[name] / 1e6)


def test_unnamed_op_takes_the_phase_around_it():
    """An op with no op_name (the compiler's own copy or loop) between
    two ops of one phase counts in that phase; between two phases it
    stays unattributed."""
    step = list(STEP)
    # the copy now runs between the two sync ops
    step[7] = ("", "copy", 470, 490)
    step[6] = (step[6][0], step[6][1], 490, 540)
    rec = _record(step)
    ctx = _ctx(rec)
    assert _metric("step.sync_ms").read(ctx) == pytest.approx(190 / 1e6)
    assert _metric("step.unattributed_ms").read(ctx) == 0.0
    assert _metric("step.device_ms").read(ctx) == pytest.approx(
        sum(_metric(n).read(ctx) or 0.0 for n in PHASE_METRICS))
    dev = rec.devices[0]
    phases = scopes.event_phases(rec, dev)
    assert phases[0] == -1                       # the while: a container
    assert scopes.PHASES[phases[7]] == "sync"


def test_phases_add_up_to_the_busy_union():
    rec = _record(chips=4)
    ctx = _ctx(rec)
    parts = sum(_metric(n).read(ctx) for n in PHASE_METRICS)
    assert _metric("step.device_ms").read(ctx) == pytest.approx(parts)
    assert parts == pytest.approx(650 / 1e6)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_without_its_scope(name):
    """A trace whose ops carry none of the reader's scopes reads None:
    here every op but the unattributed copy is taken out."""
    rec = _record([s for s in STEP if s[0] == ""])
    assert _metric(name).read(_ctx(rec)) is None


@pytest.mark.parametrize("name", ["step.optimizer_ms", "step.backward_ms",
                                  "step.remat_ms", "step.unattributed_ms",
                                  "model.head_ms", "model.attention_ms"])
def test_recorded_trace_has_no_such_scope(name):
    """The recorded trace ran a tree-combine under ``edst/`` and a reshape
    under ``model/``: no step phase, no backward, no head."""
    rec = tr.reduce_trace(str(DATA / "small_tpu.xplane.pb"),
                          [(DATA / "small_tpu.hlo.txt").read_text()])
    assert _metric(name).read(_ctx(rec)) is None


def test_wire_gb_reads_the_program_gauge(monkeypatch):
    from repro.telemetry import metrics as tm
    monkeypatch.setattr(tm, "REGISTRY", tm.MetricsRegistry())
    reader = _metric("sync.wire_gb")
    ctx = _ctx(_record())
    assert reader.read(ctx) is None              # no program traced yet
    tm.gauge("edst_wire_bytes").set(7.0, engine="striped")
    assert reader.read(ctx) is None              # not the cell's engine
    tm.note_program("pipelined", ("k",), waves=4, wire_bytes=2152240128)
    assert reader.read(ctx) == pytest.approx(2.152240128)


def test_wire_bytes_of_the_four_chip_schedule():
    """What the four-chip cell's gauge should read: the smollm-135m
    gradient (134,515,008 float32) over the (4, 1) mesh's schedule."""
    from repro.core.collectives import wave_wire_bytes
    from repro.dist.steps import edst_spec_for_mesh
    cell = chip_harness.load_cell("smollm-135m.train.edst.4chip")
    t = cell.traffic
    spec = edst_spec_for_mesh(tuple(t["mesh"]), tuple(t["axes"]),
                              engine=t["engine"])
    assert sum(wave_wire_bytes(spec, 4 * 134_515_008, 4)) == 2_152_240_128
