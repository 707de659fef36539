"""Telemetry: Chrome-trace export (schema across engines x paper
topologies, flow binding, golden regression), the metrics registry, the
recovery journal's JSONL sink, and the telemetry train-step metrics
dict (no-retrace contract included)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.analysis.verify import (ENGINES, PAPER_TOPOLOGIES,
                                   _compile_specs, _schedule_for)
from repro.launch.mesh import make_mesh
from repro.telemetry import metrics as tm
from repro.telemetry import trace as tt

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")

_SCHEDS: dict = {}
_SPECS: dict = {}


def _spec(label: str, engine: str):
    """Compile (and cache) one engine spec per paper topology; skip when
    the engine declines the fabric (per_tree without jax, etc.)."""
    if label not in _SCHEDS:
        _SCHEDS[label] = _schedule_for(label)
    key = (label, engine)
    if key not in _SPECS:
        _SPECS[key] = _compile_specs(_SCHEDS[label], (engine,))[engine]
    spec = _SPECS[key]
    if isinstance(spec, str):
        pytest.skip(spec)
    return spec


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("label", PAPER_TOPOLOGIES)
def test_trace_schema_valid(label, engine):
    """Every engine on every paper topology exports a schema-valid
    Chrome trace with at least one span per wave and matched flows."""
    spec = _spec(label, engine)
    tr = tt.trace_spec(spec, label=f"{label}/{engine}")
    assert tt.validate_trace(tr) == []
    evs = tr["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    flows_s = [e for e in evs if e["ph"] == "s"]
    flows_f = [e for e in evs if e["ph"] == "f"]
    assert spans, "no spans"
    assert len(flows_s) == len(flows_f)
    waves = {e["args"]["wave"] for e in spans}
    assert waves == set(range(len(waves))), "missing wave indices"
    # spans carry the byte accounting the CostModel predicted from
    assert all(e["args"]["bytes"] >= 0 and e["args"]["wire_bytes"] >= 0
               for e in spans)


def test_trace_flows_follow_happens_before():
    """Flow arrows bind producer->consumer pairs: every flow-finish lands
    at or after its flow-start (Perfetto renders backwards arrows as
    broken), and ids pair exactly once."""
    spec = _spec("torus4x4", "pipelined")
    _, msgs = tt.spec_messages(spec)
    edges = tt.happens_before(msgs)
    assert edges, "torus4x4 pipelined must have cross-wave dependencies"
    for prod, cons in edges:
        assert msgs[prod][0] < msgs[cons][0], "flow within a single wave"
        # the consumer's source must have heard from the producer's tree
        assert msgs[prod][1] == msgs[cons][1]
        assert msgs[prod][4] == msgs[cons][3]


def test_trace_lane_modes_agree_on_spans():
    spec = _spec("torus4x4", "striped")
    by_dev = tt.trace_spec(spec, lane="device")
    by_tree = tt.trace_spec(spec, lane="tree")
    n_dev = sum(e["ph"] == "X" for e in by_dev["traceEvents"])
    n_tree = sum(e["ph"] == "X" for e in by_tree["traceEvents"])
    assert n_dev == n_tree
    assert tt.validate_trace(by_tree) == []


def test_trace_golden_torus4x4_pipelined():
    """Byte-exact regression vs the committed golden trace: timings come
    from the default CostModel constants and 3-decimal rounding, so any
    diff is a real change to the exporter or the schedule compiler."""
    spec = _spec("torus4x4", "pipelined")
    tr = tt.trace_spec(spec, label="torus4x4/pipelined")
    with open(os.path.join(GOLDEN, "trace_torus4x4_pipelined.json")) as f:
        golden = json.load(f)
    assert tr == golden


def test_trace_runtime_renders_entry_table():
    from repro.dist.steps import fault_runtime_for_mesh
    rt = fault_runtime_for_mesh((16, 1), ("data", "model"),
                                dp_torus_shape=(4, 4))
    tr = tt.trace_runtime(rt, nbytes=1 << 12)
    assert tt.validate_trace(tr) == []
    pids = {e["pid"] for e in tr["traceEvents"] if e["ph"] == "X"}
    assert len(pids) >= 2, "one lane group per precompiled failure class"


def test_trace_validator_catches_breakage():
    spec = _spec("torus4x4", "fused")
    tr = tt.trace_spec(spec)
    ok = json.loads(json.dumps(tr))
    ok["traceEvents"][-1]["ts"] = -1.0
    assert tt.validate_trace(ok)
    bad = json.loads(json.dumps(tr))
    for e in bad["traceEvents"]:
        if e["ph"] == "f":
            e["id"] += 10_000   # orphan every flow finish
    assert tt.validate_trace(bad)


def test_trace_cli_writes_and_validates(tmp_path):
    out = tmp_path / "tr.json"
    rc = tt.main(["--topology", "torus4x4", "--engine", "striped",
                  "--out", str(out), "--validate"])
    assert rc == 0
    tr = json.loads(out.read_text())
    assert tt.validate_trace(tr) == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counter_gauge_histogram():
    reg = tm.MetricsRegistry()
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(2.0, engine="striped")
    assert c.value() == 1.0
    assert c.value(engine="striped") == 2.0
    g = reg.gauge("g", "help")
    g.set(3.5, dev="0")
    g.inc(0.5, dev="0")
    assert g.value(dev="0") == 4.0
    h = reg.histogram("h_us", "help", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    snap = reg.snapshot()
    assert snap["h_us"]["values"][0]["value"]["count"] == 3
    assert reg.counter("c_total") is c, "registry must be idempotent"
    with pytest.raises(TypeError):
        reg.gauge("c_total")


def test_metrics_prometheus_text_shape():
    reg = tm.MetricsRegistry()
    reg.counter("edst_x_total", "things").inc(3, kind="a b")
    text = reg.prometheus_text()
    assert "# TYPE edst_x_total counter" in text
    assert 'edst_x_total{kind="a b"} 3' in text


def test_note_program_counts_traces_and_retraces():
    tm.reset()
    tm.note_program("pipelined", ("k1",), waves=4, wire_bytes=100)
    tm.note_program("pipelined", ("k1",), waves=4, wire_bytes=100)
    tm.note_program("pipelined", ("k2",), waves=4, wire_bytes=100)
    vals = tm.counter_values("edst_program_traces_total")
    assert vals[(("engine", "pipelined"),)] == 3.0
    re = tm.counter_values("edst_retrace_detections_total")
    assert re.get((("engine", "pipelined"),), 0.0) == 1.0
    tm.reset()


def test_executor_note_trace_fires(monkeypatch):
    """A jitted pipelined allreduce records exactly one program trace and
    flags a retrace when the same (engine, key, bytes) traces twice."""
    from repro.core import topologies as topo
    from repro.core.collectives import (allreduce_schedule,
                                        pipelined_spec_from_schedule)
    from repro.core.edst_star import star_edsts
    from repro.dist.tree_allreduce import _note_trace
    tm.reset()
    sp = topo.device_topology((2, 2))
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = pipelined_spec_from_schedule(sched, ("data",))
    x = jnp.ones((8,), jnp.float32)
    _note_trace("pipelined", spec, x)
    assert tm.counter_values("edst_program_traces_total")[
        (("engine", "pipelined"),)] == 1.0
    assert tm.counter_values("edst_retrace_detections_total") == {}
    _note_trace("pipelined", spec, x)
    assert tm.counter_values("edst_retrace_detections_total")[
        (("engine", "pipelined"),)] == 1.0
    tm.reset()


def test_note_trace_sets_segments_gauge():
    """The pipelined engine's trace hook records the segment count S it
    compiled beside the wire bytes; an engine that streams no segments
    leaves the gauge unset."""
    from repro.core import topologies as topo
    from repro.core.collectives import (allreduce_schedule,
                                        pipelined_spec_from_schedule)
    from repro.core.edst_star import star_edsts
    from repro.dist.tree_allreduce import _note_trace
    tm.reset()
    sp = topo.device_topology((2, 2))
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = pipelined_spec_from_schedule(sched, ("data",))
    x = jnp.ones((8,), jnp.float32)
    _note_trace("pipelined", spec, x, segments=4)
    _note_trace("fused", spec, x)
    gauge = tm.REGISTRY.get("edst_segments")
    assert gauge.value(engine="pipelined") == 4.0
    assert gauge.value(engine="fused") is None
    assert tm.REGISTRY.get("edst_wire_bytes").value(engine="pipelined")
    tm.reset()


# ---------------------------------------------------------------------------
# recovery journal JSONL sink
# ---------------------------------------------------------------------------

def _controller(tmp_path, journal=True):
    from repro.dist.recovery import RecoveryController
    from repro.dist.steps import fault_runtime_for_mesh
    rt = fault_runtime_for_mesh((4, 1), ("data", "model"),
                                dp_torus_shape=(2, 2))
    path = str(tmp_path / "journal.jsonl") if journal else None
    return RecoveryController(rt, journal_path=path), path


def test_journal_jsonl_sink_monotonic_and_replayable(tmp_path):
    from repro.dist.recovery import load_journal, replay_journal
    ctrl, path = _controller(tmp_path)
    ctrl._journal(0, "probe_failure", "flip", 0, 1, 2, 0.5,
                  detail={"x": 1})
    ctrl._journal(5, "probe_failure", "flip", 1, 0, 1, None)
    rows = [json.loads(line) for line in open(path)]
    assert [r["seq"] for r in rows] == [0, 1]
    entries = load_journal(path)
    assert len(entries) == 2 and entries[1].to_schedule == 0
    # file form and in-memory form replay identically
    assert replay_journal(path) == replay_journal(ctrl.journal)


def test_journal_load_rejects_non_monotonic(tmp_path):
    from repro.dist.recovery import load_journal
    ctrl, path = _controller(tmp_path)
    ctrl._journal(0, "probe_failure", "flip", 0, 1, 0, None)
    row = json.loads(open(path).read())   # replay seq 0: not monotonic
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")
    with pytest.raises(ValueError):
        load_journal(path)


def test_journal_metric_reconciles_with_file(tmp_path):
    tm.reset()
    ctrl, path = _controller(tmp_path)
    ctrl._journal(0, "probe_failure", "flip", 0, 1, 0, None)
    ctrl._journal(1, "straggler", "backoff", 1, 1, 0, None)
    ctrl._journal(2, "probe_failure", "flip", 1, 2, 0, None)
    vals = tm.counter_values("edst_recovery_transitions_total")
    by_pair: dict = {}
    for line in open(path):
        r = json.loads(line)
        key = (("action", r["action"]), ("cause", r["cause"]))
        by_pair[key] = by_pair.get(key, 0.0) + 1.0
    assert vals == by_pair
    tm.reset()


# ---------------------------------------------------------------------------
# train-step telemetry dict
# ---------------------------------------------------------------------------

def test_telemetry_dict_single_device_no_retrace():
    """telemetry=True returns the structured sync metrics dict on the
    non-manual path too, and two distinct batches reuse one trace: the
    state is placed and the step jitted as the train launcher does it."""
    from repro.dist.sharding import train_state_shardings
    from repro.dist.steps import make_train_step
    from repro.models.api import build
    from repro.optim import AdamW, cosine_schedule
    cfg = configs.get("smollm-135m").reduced()
    api = build(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamW(cosine_schedule(1e-3, 5, 50))
    params, axes = api.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    shards = train_state_shardings(axes, params, mesh)
    params, opt_state = jax.device_put((params, opt_state), shards)
    with jax.set_mesh(mesh):
        jstep = jax.jit(make_train_step(api, opt, mesh, telemetry=True),
                        out_shardings=(*shards, None))
        for i in range(2):
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(i), (8, 65), 0, cfg.vocab)}
            params, opt_state, m = jstep(params, opt_state, batch)
    assert jstep._cache_size() == 1, "telemetry dict must not retrace"
    for key in ("sync_dev", "sync_grad_norm", "sync_schedule_id",
                "sync_wire_bytes"):
        assert key in m, key
    assert float(m["sync_grad_norm"]) > 0.0
    assert int(m["sync_schedule_id"]) == 0
    assert float(m["sync_wire_bytes"]) == 0.0   # no manual sync program


EDST_TELEMETRY_CODE = r"""
import jax, jax.numpy as jnp
from repro import configs
from repro.core.collectives import wave_wire_bytes
from repro.models.api import build
from repro.dist.steps import fault_runtime_for_mesh, make_train_step
from repro.optim import AdamW, cosine_schedule
from repro.launch.mesh import make_mesh

cfg = configs.get('smollm-135m').reduced()
api = build(cfg)
mesh = make_mesh((16, 1), ('data', 'model'))
rt = fault_runtime_for_mesh((16, 1), ('data', 'model'), dp_torus_shape=(4, 4))
opt = AdamW(cosine_schedule(1e-3, 10, 100))
params, _ = api.init(jax.random.PRNGKey(0))
opt_state = opt.init(params)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (16, 65), 0,
                                      cfg.vocab)}
step = make_train_step(api, opt, mesh, mode='edst', fault_runtime=rt,
                       telemetry=True)
jstep = jax.jit(step)
with jax.set_mesh(mesh):
    p, o, m = jstep(params, opt_state, batch, jnp.int32(rt.active))
    # second call reaches the steady-state sharding of the train loop
    # (step 1's outputs feed step 2); only then is the cache size the
    # no-retrace baseline a schedule flip must preserve
    p, o, m = jstep(p, o, batch, jnp.int32(rt.active))
    traces = jstep._cache_size()
    # flip to a degraded schedule: gauge moves, executable does not
    sid_flip = None
    for i, e in enumerate(rt.entries):
        if i != rt.active and e.k > 0:
            sid_flip = i
            break
    p, o, m2 = jstep(p, o, batch, jnp.int32(sid_flip))
    assert jstep._cache_size() == traces, 'schedule flip retraced'
wire0, wire1 = float(m['sync_wire_bytes']), float(m2['sync_wire_bytes'])
flat = sum(int(x.size) for x in jax.tree.leaves(p))
e0, e1 = rt.entries[rt.active], rt.entries[sid_flip]
want0 = float(sum(wave_wire_bytes(e0.spec, flat * 4, 4,
                                  e0.fractions or None)))
want1 = float(sum(wave_wire_bytes(e1.spec, flat * 4, 4,
                                  e1.fractions or None)))
assert abs(wire0 - want0) < 1e-3 * max(1.0, want0), (wire0, want0)
assert abs(wire1 - want1) < 1e-3 * max(1.0, want1), (wire1, want1)
assert wire0 != wire1, 'gauge must move with the schedule id'
assert float(m['sync_grad_norm']) > 0.0
print('EDST_TELEMETRY_OK')
"""


def test_telemetry_dict_edst_wire_gauge_tracks_schedule(subproc):
    out = subproc(EDST_TELEMETRY_CODE, 16)
    assert "EDST_TELEMETRY_OK" in out


# ---------------------------------------------------------------------------
# named scopes of the train step (what a device profile's ops carry)
# ---------------------------------------------------------------------------

def _op_names(hlo_text: str) -> set:
    import re
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


@pytest.fixture(scope="module")
def step_op_names():
    """op_names of the reduced smollm step compiled on one device, as
    the train launcher jits it."""
    from repro.dist.sharding import train_state_shardings
    from repro.dist.steps import make_train_step
    from repro.models.api import build
    from repro.optim import AdamW, cosine_schedule
    cfg = configs.get("smollm-135m").reduced()
    api = build(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamW(cosine_schedule(1e-3, 5, 50))
    params, axes = api.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    shards = train_state_shardings(axes, params, mesh)
    params, opt_state = jax.device_put((params, opt_state), shards)
    batch = {"tokens": jnp.zeros((4, 33), jnp.int32)}
    with jax.set_mesh(mesh):
        jstep = jax.jit(make_train_step(api, opt, mesh, mode="gspmd"),
                        out_shardings=(*shards, None))
        text = jstep.lower(params, opt_state, batch).compile().as_text()
    return _op_names(text)


@pytest.mark.parametrize("what, holds", [
    ("forward", lambda n: "jvp(step/model)" in n and "transpose(" not in n),
    ("backward", lambda n: "transpose(jvp(step/model))" in n),
    ("recomputation", lambda n: "rematted_computation" in n),
    ("optimizer", lambda n: "step/optimizer" in n),
    ("embed", lambda n: "model/embed/" in n),
    ("attention", lambda n: "model/attention/" in n),
    ("mlp", lambda n: "model/mlp/" in n),
    ("head", lambda n: "model/head/" in n),
])
def test_step_scopes_reach_the_compiled_program(step_op_names, what, holds):
    """Each phase and sublayer of the step names some op of the compiled
    program; no op of the one-device step is under a sync scope."""
    assert any(holds(n) for n in step_op_names), what
    assert not any("step/sync" in n or "edst/" in n for n in step_op_names)


EDST_SCOPES_CODE = r"""
import re
import jax, jax.numpy as jnp
from repro import configs
from repro.core.collectives import wave_wire_bytes
from repro.dist.sharding import train_state_shardings
from repro.dist.steps import edst_spec_for_mesh, make_train_step
from repro.launch.mesh import make_mesh
from repro.models.api import build
from repro.optim import AdamW, cosine_schedule
from repro.telemetry import metrics as tm

cfg = configs.get('smollm-135m').reduced()
api = build(cfg)
mesh = make_mesh((4, 1), ('data', 'model'))
opt = AdamW(cosine_schedule(1e-3, 5, 50))
params, axes = api.init(jax.random.PRNGKey(0))
opt_state = opt.init(params)
shards = train_state_shardings(axes, params, mesh)
params, opt_state = jax.device_put((params, opt_state), shards)
batch = {'tokens': jnp.zeros((8, 33), jnp.int32)}
with jax.set_mesh(mesh):
    jstep = jax.jit(make_train_step(api, opt, mesh, mode='edst'),
                    out_shardings=(*shards, None))
    text = jstep.lower(params, opt_state, batch).compile().as_text()
names = set(re.findall(r'op_name="([^"]*)"', text))
waves = [n for n in names if 'edst/' in n]
assert waves, 'no op under an edst/ wave scope'
outside = [n for n in waves if 'step/sync' not in n]
assert not outside, outside[:5]
assert any('step/optimizer' in n for n in names)
assert any('transpose(jvp(step/model))' in n for n in names)
spec = edst_spec_for_mesh((4, 1), ('data', 'model'))
flat = sum(int(x.size) for x in jax.tree.leaves(params))
want = sum(wave_wire_bytes(spec, flat * 4, 4))
got = tm.REGISTRY.get('edst_wire_bytes').value(engine='pipelined')
assert got == want, (got, want)
print('EDST_SCOPES_OK', len(waves), got)
"""


def test_step_scopes_edst_waves_under_sync(subproc):
    """On four devices every EDST wave op is also under ``step/sync``,
    and the program's wire-bytes gauge is the schedule's sum."""
    out = subproc(EDST_SCOPES_CODE, 4)
    assert "EDST_SCOPES_OK" in out


def test_train_profile_has_host_spans(tmp_path):
    """``train.py --profile-dir`` puts each step and its input, dispatch,
    read-back and checkpoint on the profiler's host plane."""
    from jax.profiler import ProfileData
    from repro.launch.train import main as train_main
    prof = tmp_path / "prof"
    train_main(["--arch", "smollm-135m", "--reduced", "--steps", "2",
                "--batch", "2", "--seq", "16", "--mesh", "1,1",
                "--log-every", "1", "--ckpt-dir", str(tmp_path / "ck"),
                "--ckpt-every", "1", "--profile-dir", str(prof)])
    path = next(prof.rglob("*.xplane.pb"))
    names = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            names += [ev.name for line in plane.lines for ev in line.events]
    for span in ("train/input", "train/dispatch", "train/readback",
                 "train/checkpoint"):
        assert names.count(span) == 2, (span, names.count(span))
    assert names.count("train") == 2
