"""The trace reduction and the per-layer metric readers, on a small trace
recorded on a TPU v5 lite: three steps of a jitted function that runs
the tree-combine kernel under an ``edst/`` named scope, with the
benchmark's host spans around each step and its input."""
import dataclasses
import types

import numpy as np
import pytest

from chipbench_paths import BENCH, DATA

import chip_harness
import trace_reduce as tr

TRACE = DATA / "small_tpu.xplane.pb"
HLO = (DATA / "small_tpu.hlo.txt").read_text()


@pytest.fixture(scope="module")
def rec():
    return tr.reduce_trace(str(TRACE), [HLO])


def _events():
    """Every XLA Ops event of the trace, read without the reducer."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(TRACE)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                             e.name) for e in line.events]
    return out


def test_window_steps_and_spans(rec):
    assert rec.steps == 3
    assert rec.window == (43654956, 49542366)
    inputs = rec.host_spans["bench/input"]
    assert inputs.shape == (3, 2)
    assert len(rec.devices) == 1 and rec.devices[0].device == 0


def test_busy_time_is_the_union_of_op_intervals(rec):
    evs = sorted(_events())
    # the ops of one step never overlap here, so the union is their sum
    want = sum(min(e, rec.window[1]) - max(s, rec.window[0])
               for s, e, _ in evs if e > rec.window[0] and s < rec.window[1])
    assert rec.busy_ns(rec.devices[0]) == want


def test_ops_shapes_and_scopes(rec):
    ops = {o.name: o for o in rec.ops}
    assert set(ops) == {"tree_combine.1", "reshape.2", "fusion",
                        "broadcast_multiply_fusion"}
    tc = ops["tree_combine.1"]
    assert tc.opcode == "custom-call"
    assert tc.operand_arrays() == [("f32", (1, 1048576), 0),
                                   ("f32", (1048576,), 0)]
    assert tc.result_arrays() == [("f32", (1048576,), 1)]
    assert tr.nbytes(tc.operand_arrays(), space=0) == 8 * 2 ** 20
    assert tc.op_name.startswith("jit(step)/edst/t0/w1/reduce")
    assert ops["reshape.2"].op_name == "jit(step)/model/reshape"
    assert ops["fusion"].op_name == ""


def test_parse_op_tuple_result():
    op = tr.parse_op("%while.3 = (s32[]{:T(128)}, bf16[8,2048]{1,0:T(8,128)"
                     "(2,1)S(1)}) while((s32[], bf16[8,2048]) %t), "
                     "condition=%c, body=%b")
    assert (op.name, op.opcode) == ("while.3", "while")
    assert tr.parse_arrays(op.result) == [("s32", (), 0),
                                          ("bf16", (8, 2048), 1)]
    assert tr.parse_arrays(op.operands) == [("s32", (), 0),
                                            ("bf16", (8, 2048), 0)]


@pytest.mark.parametrize("a, b, union, overlap", [
    ([(0, 10), (5, 20), (30, 40)], [(15, 35)], 30, 10),
    ([(0, 5)], [(5, 10)], 5, 0),
    ([(0, 100)], [(10, 20), (30, 40)], 100, 20),
])
def test_interval_arithmetic(a, b, union, overlap):
    sa, ea = (np.asarray(x, np.int64) for x in zip(*a))
    sb, eb = (np.asarray(x, np.int64) for x in zip(*b))
    assert tr.union_length(sa, ea) == union
    assert tr.intersection_length(tr.merge(sa, ea), tr.merge(sb, eb)) \
        == overlap


# sizes whose gradient the recorded combine's 2**20 elements a step cover
SMALL = dict(d_model=8, n_heads=2, n_kv=1, head_dim=4, d_ff=16, vocab=32,
             n_layers=2)


def _ctx(rec, peaks=True, sizes=SMALL):
    cell = chip_harness.load_cell("smollm-135m.train.edst.4chip")
    table = chip_harness.load_json(BENCH / "peaks.json")
    return chip_harness.MetricContext(
        rec, cell, types.SimpleNamespace(sizes=sizes), 1000.0,
        chip_harness.peaks_for(table, "TPU v5 lite") if peaks else None)


def _metric(name):
    return chip_harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_metric_readers_by_hand(rec):
    ctx = _ctx(rec)
    # the three bench/input spans of the recording, by hand
    want_input = (196680 + 166590 + 150630) / 3 / 1e6
    assert _metric("host.input_ms").read(ctx) == pytest.approx(want_input)
    busy = rec.busy_ns(rec.devices[0])
    assert _metric("device.idle_pct").read(ctx) == pytest.approx(
        100 * (1 - busy / (49542366 - 43654956)))
    assert _metric("step.device_ms").read(ctx) == pytest.approx(
        busy / 3 / 1e6)
    lo, hi = rec.window
    tc = [(s, e) for s, e, n in _events() if n.startswith("%tree_combine.1")]
    # the device clock runs a little ahead of the host spans here, so the
    # first call starts before the first step span: count what is inside
    sync = sum(max(0, min(e, hi) - max(s, lo)) for s, e in tc)
    assert len(tc) == 3 and sync > 0
    assert _metric("sync.edst_ms").read(ctx) == pytest.approx(sync / 3 / 1e6)
    # the combine overlaps no other op: all of it is exposed
    assert _metric("sync.exposed_ms").read(ctx) == pytest.approx(
        sync / 3 / 1e6)
    # the combine is the only kernel call, and all of the sync
    assert _metric("kernel.tree_combine_ms").read(ctx) == pytest.approx(
        sync / 3 / 1e6)
    # a call reads (1, 2**20) and (2**20,) f32 and writes (2**20,) f32;
    # two of the three calls overlap the window
    n_in = sum(1 for s, e in tc if e > lo and s < hi)
    assert n_in == 2
    assert _metric("kernel.tree_combine_gb").read(ctx) == pytest.approx(
        n_in * 3 * 4 * 2 ** 20 / 3 / 1e9)


def test_kernel_metrics_see_calls_that_miss_the_sync_volume(rec, capsys):
    # smollm-135m's gradient over four chips: the one recorded call a
    # step writes far less than the all-reduce has to combine
    four = dataclasses.replace(rec, devices=rec.devices * 4)
    sizes = dict(d_model=576, n_heads=9, n_kv=3, head_dim=64, d_ff=1536,
                 vocab=49152, n_layers=30)
    ctx = _ctx(four, sizes=sizes)
    for name in ("kernel.tree_combine_ms", "kernel.tree_combine_gb"):
        assert _metric(name).read(ctx) is None
    assert "kernel metrics left out" in capsys.readouterr().err
    # the same four chips with a gradient that the calls cover
    assert _metric("kernel.tree_combine_ms").read(_ctx(four)) > 0


def test_per_step_counts_the_events_in_the_window(rec):
    dev = rec.devices[0]
    calls = rec.per_step(dev, lambda op: op.name == "tree_combine.1",
                         lambda op: 1.0)
    # the first call ends before the first step span starts
    assert calls == pytest.approx(2 / 3)


def test_readers_without_what_they_read(rec):
    ctx = _ctx(rec, peaks=False)
    assert _metric("step.mfu").read(ctx) is None
    plain = tr.reduce_trace(str(TRACE))      # no HLO: no scope is known
    assert _metric("sync.edst_ms").read(_ctx(plain)) is None
    assert _metric("sync.exposed_ms").read(_ctx(plain)) is None


def test_breakdown(rec):
    ops = tr.top_ops(rec, rec.devices[0])
    assert [o[0].split()[0] for o in ops][:2] == ["fusion", "tree_combine.1"]
    gaps = tr.idle_gaps(rec, rec.devices[0])
    assert len(gaps) <= 10 and all(g[1] > 0 for g in gaps)
    assert gaps[0][0].startswith("bench/")
