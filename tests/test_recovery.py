"""The escalation ladder of :class:`repro.dist.recovery.RecoveryController`
driven by scripted :class:`HealthReport` ticks: flap -> retry, kill ->
precompiled flip, out-of-class burst -> rebuild + hot-swap, corruption ->
redo (escalating to rebuild), node loss -> checkpoint + rescale (or a
loud stall without callbacks).  Plus the journal-replay audit and the
``sid-out-of-range`` verifier code both the journal gate and the traced
debug switch share.  Controller tests are host-only; the traced debug
guard runs a 4-device subprocess (direct ``run_with_devices``, fast
tier)."""
import numpy as np
import pytest

from conftest import run_with_devices
from repro.analysis.verify import check_schedule_id
from repro.dist.chaos import out_of_class_burst
from repro.dist.fault import NoScheduleError
from repro.dist.health import HealthReport, compile_link_probe
from repro.dist.recovery import (RecoveryController, RecoveryPolicy,
                                 replay_journal)
from repro.dist.steps import fault_runtime_for_mesh
from repro.launch.elastic import rescale_after_node_loss


@pytest.fixture(scope="module")
def rt():
    return fault_runtime_for_mesh((16, 1), ("data", "model"),
                                  dp_torus_shape=(4, 4))


def _report(plan, step, dead_edges=(), checksum_dev=0.0, straggler=False):
    """A HealthReport as the probe would produce it with the given
    canonical edges dead (both directions fail)."""
    dead = frozenset(dead_edges)
    from repro.core.graph import canon
    ok = np.array([canon(s, d) not in dead for s, d in plan.links])
    return HealthReport(step=step, links=plan.links, link_ok=ok,
                        checksum_dev=checksum_dev, straggler=straggler)


def _tree_edge(rt, j=0):
    return next(iter(sorted(rt.entries[0].sched.trees[j].tree)))


def test_flap_retries_then_journals_clean(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(rt)
    edge = _tree_edge(rt)
    dec = ctrl.observe(_report(plan, 0, {edge}))
    assert dec.action == "retry" and dec.stall and dec.backoff_s > 0
    assert ctrl.state == "suspect" and not ctrl.journal
    dec = ctrl.observe(_report(plan, 1))           # next probe clean
    assert dec.action == "none" and not dec.stall
    assert ctrl.state == "healthy"
    (e,) = ctrl.journal
    assert e.cause == "link-flap" and e.action == "retry"
    assert e.steps_degraded == 1
    assert ctrl.schedule_id == 0                   # no flip for a flap


def test_kill_confirms_then_flips_schedule(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(rt)
    edge = _tree_edge(rt)
    assert ctrl.observe(_report(plan, 0, {edge})).stall
    dec = ctrl.observe(_report(plan, 1, {edge}))   # outlives tolerance
    assert dec.action == "flip" and not dec.stall
    assert dec.detail["from_schedule"] == 0
    assert ctrl.schedule_id != 0
    assert not ctrl.runtime.entry.uses_link(frozenset({edge}))
    (e,) = ctrl.journal
    assert e.cause == "link-kill" and e.action == "flip"
    assert e.steps_degraded == 1 and e.mttr_s >= 0
    assert replay_journal(ctrl.journal) == (ctrl.generation,
                                            ctrl.schedule_id)


def test_burst_escalates_to_rebuild_and_hot_swap(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(
        rt, RecoveryPolicy(background_rebuild=False))
    burst = out_of_class_burst(rt, np.random.default_rng(0))
    assert ctrl.observe(_report(plan, 0, burst)).stall     # suspects
    dec = ctrl.observe(_report(plan, 1, burst))            # confirmed
    assert dec.action == "rebuild" and dec.stall           # repacking
    dec = ctrl.observe(_report(plan, 2, burst))
    assert dec.action == "hot-swap" and dec.runtime_changed
    assert ctrl.generation == 1
    assert ctrl.runtime is not rt and ctrl.runtime.k >= 1
    # the repack avoids every dead link
    assert not ctrl.runtime.entry.uses_link(frozenset(burst))
    (e,) = ctrl.journal
    assert e.cause == "link-burst" and e.action == "hot-swap"
    assert replay_journal(ctrl.journal) == (ctrl.generation,
                                            ctrl.schedule_id)


def test_corruption_redoes_then_escalates(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(
        rt, RecoveryPolicy(max_retries=2, background_rebuild=False))
    dec = ctrl.observe(_report(plan, 0, checksum_dev=0.5))
    assert dec.action == "retry" and dec.redo_step and not dec.stall
    assert ctrl.journal[-1].cause == "payload-corruption"
    # a clean tick resets the retry budget
    assert ctrl.observe(_report(plan, 1)).action == "none"
    for s in (2, 3):
        assert ctrl.observe(_report(plan, s, checksum_dev=0.5)).redo_step
    dec = ctrl.observe(_report(plan, 4, checksum_dev=0.5))
    assert dec.action == "rebuild" and dec.stall   # budget exhausted
    dec = ctrl.observe(_report(plan, 5))
    assert dec.action == "hot-swap" and dec.runtime_changed
    assert ctrl.journal[-1].cause == "payload-corruption"
    assert ctrl.journal[-1].action == "hot-swap"


def test_straggler_is_journaled_not_recovered(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(rt)
    dec = ctrl.observe(_report(plan, 0, straggler=True))
    assert dec.action == "none" and not dec.stall
    (e,) = ctrl.journal
    assert e.cause == "straggler" and e.action == "observe"
    assert ctrl.schedule_id == 0


def test_node_loss_without_rescale_stalls_loudly(rt):
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(rt)
    v = plan.links[0][0]
    dead = {e for s, d in plan.links if v in (s, d)
            for e in [tuple(sorted((s, d)))]}
    rep = _report(plan, 0, dead)
    assert v in rep.node_suspects()
    for s in range(3):                 # stalls forever, journals once
        dec = ctrl.observe(_report(plan, s, dead))
        assert dec.action == "rescale" and dec.stall
        assert ctrl.state == "stalled"
    (e,) = ctrl.journal
    assert e.cause == "node-loss" and e.action == "observe"
    assert "error" in e.detail


def test_node_loss_checkpoints_then_rescales(rt):
    plan = compile_link_probe(rt)
    calls = []

    def on_checkpoint():
        calls.append("ckpt")

    def on_rescale(event):
        calls.append("rescale")
        new_rt, _ = rescale_after_node_loss(rt, event)
        return new_rt

    ctrl = RecoveryController(rt, on_checkpoint=on_checkpoint,
                              on_rescale=on_rescale)
    v = plan.links[0][0]
    dead = {tuple(sorted((s, d))) for s, d in plan.links if v in (s, d)}
    dec = ctrl.observe(_report(plan, 0, dead))
    assert dec.action == "rescale" and dec.runtime_changed
    assert calls == ["ckpt", "rescale"]      # checkpoint BEFORE rescale
    assert ctrl.generation == 1
    assert ctrl.runtime.graph.n == rt.graph.n - 1
    (e,) = ctrl.journal
    assert e.cause == "node-loss" and e.action == "rescale"
    assert replay_journal(ctrl.journal) == (ctrl.generation,
                                            ctrl.schedule_id)


def test_journal_replays_full_scenario(rt):
    """flap -> kill -> burst in one session: the journal alone recovers
    the final (generation, schedule id) the live controller holds."""
    plan = compile_link_probe(rt)
    ctrl = RecoveryController(
        rt, RecoveryPolicy(background_rebuild=False))
    edge = _tree_edge(rt)
    ctrl.observe(_report(plan, 0, {edge}))
    ctrl.observe(_report(plan, 1))                      # flap clears
    ctrl.observe(_report(plan, 2, {edge}))
    ctrl.observe(_report(plan, 3, {edge}))              # kill -> flip
    burst = out_of_class_burst(rt, np.random.default_rng(1),
                               already_dead=frozenset({edge}))
    dead = set(burst) | {edge}
    ctrl.observe(_report(plan, 4, dead))
    ctrl.observe(_report(plan, 5, dead))                # rebuild
    ctrl.observe(_report(plan, 6, dead))                # hot-swap
    assert [e.cause for e in ctrl.journal] == [
        "link-flap", "link-kill", "link-burst"]
    assert replay_journal(ctrl.journal) == (ctrl.generation,
                                            ctrl.schedule_id)
    assert ctrl.generation == 1


def test_check_schedule_id_names_the_violation(rt):
    assert check_schedule_id(5, 0) is None
    assert check_schedule_id(5, 4) is None
    for bad in (-1, 5, 99):
        v = check_schedule_id(5, bad)
        assert v is not None and v.code == "sid-out-of-range"
        assert str(bad) in v.detail
    # the journal gate: a controller can never record a bogus flip
    ctrl = RecoveryController(rt)
    with pytest.raises(NoScheduleError):
        ctrl._journal(0, "link-kill", "flip", 0, len(rt.entries), 0, 0.0)


DEBUG_SID_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist.steps import fault_runtime_for_mesh
from repro.launch.mesh import make_mesh

rt = fault_runtime_for_mesh((4, 1), ('data', 'model'), dp_torus_shape=(2, 2))
mesh = make_mesh((4, 1), ('data', 'model'))

def harness(sync):
    def body(xs, sid):
        return sync(xs.reshape(xs.shape[1:]), sid)[None]
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P('data'), P()),
                                 out_specs=P('data'), axis_names={'data'},
                                 check_vma=False))

x = jnp.ones((4, 8), jnp.float32)
bad_sid = jnp.int32(len(rt.entries) + 3)

f = harness(rt.make_allreduce(debug=True))
ok = f(x, jnp.int32(0))
assert bool(jnp.isfinite(ok).all()) and jnp.allclose(ok, 4.0), ok
poisoned = f(x, bad_sid)      # traced guard: NaN-poison, not a wrong sum
assert bool(jnp.isnan(poisoned).all()), poisoned

g = harness(rt.make_allreduce())   # debug off: lax.switch clamps silently
clamped = g(x, bad_sid)
assert bool(jnp.isfinite(clamped).all()), clamped
print("DEBUG_SID_OK")
"""


def test_debug_switch_poisons_out_of_range_sid():
    """S2: with ``debug=True`` the traced twin of ``check_schedule_id``
    turns lax.switch's silent clamp into a NaN-poisoned result (plus a
    device print); the default path keeps the clamp semantics."""
    out = run_with_devices(DEBUG_SID_CODE, 4)
    assert "DEBUG_SID_OK" in out


def test_rescale_onto_same_fabric_reuses_cached_specs(rt):
    """Elastic spec-cache reuse (the no-retrace contract): two rescales
    landing on the SAME surviving fabric share every compiled entry spec
    object -- jitted executors keyed on the spec never recompile -- while
    history stays per-runtime."""
    from repro.core.fault import FailureEvent
    ev = FailureEvent(nodes=frozenset({3}))
    a, rel_a = rescale_after_node_loss(rt, ev)
    b, rel_b = rescale_after_node_loss(rt, ev)
    assert rel_a == rel_b
    assert b is not a                       # fresh runtime per event...
    assert b.entries is a.entries           # ...sharing the cached entries
    assert all(ea.spec is eb.spec
               for ea, eb in zip(a.entries, b.entries))
    assert a.history == b.history == rt.history + [("rescaled",
                                                    rt.graph.n - 1)]


def test_edst_spec_for_mesh_schedule_strategies_cached():
    """``edst_spec_for_mesh`` returns the identical object per
    (mesh, engine, schedule) across calls for EVERY strategy, and the
    strategies compile distinct specs (distinct cache keys)."""
    from repro.dist.steps import edst_spec_for_mesh
    args = ((16, 1), ("data", "model"))
    specs = {}
    for schedule in ("greedy", "search", "composed"):
        s1 = edst_spec_for_mesh(*args, dp_torus_shape=(4, 4),
                                engine="striped", schedule=schedule)
        s2 = edst_spec_for_mesh(*args, dp_torus_shape=(4, 4),
                                engine="striped", schedule=schedule)
        assert s1 is s2
        specs[schedule] = s1
    assert len({s.key for s in specs.values()}) == 3
    assert specs["composed"].key[-1] == "composed"
    assert specs["search"].key[-2:] == ("search", 0)
    assert len(specs["search"].waves) <= len(specs["greedy"].waves)
