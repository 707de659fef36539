"""ZeRO-1 differential suite: the sharded train step against dense
``psum_dp``.

The claim under test is the module docstring of
:mod:`repro.optim.sharded`: reduce-scatter grads -> owner-stripe AdamW ->
allgather params reproduces the dense optimizer exactly (up to float
reassociation of the global norm).  Each test spawns a 16-fake-device
subprocess (4x4 torus DP fabric) and trains both steps side by side on
the same quadratic toy problem, asserting per-step loss / grad-norm
agreement:

  * fast tier -- f32 wires through the *fault runtime* path, including a
    mid-run link kill: flip the traced schedule id to the degraded
    class, re-shard ``mu`` / ``nu`` with
    :meth:`FaultAwareAllreduce.reshard_owned`, keep training, and assert
    the jit cache did not grow (the flip is retrace-free);
  * fast tier -- the wave-count acceptance: the compiled zero1 step's
    HLO carries ``rs_waves + ag_waves`` ppermutes, strictly fewer than
    the composed striped allreduce step's, checked with
    ``hlo_contract_for(phase=...)`` / ``lint_hlo``;
  * slow tier -- the int8 gradient wire (``codec="full"``; params
    allgather stays full precision by design) at loosened tolerance,
    and an ``m < n`` payload (7 elements on 16 devices) where most
    stripe rows are padding.

The fast tests call :func:`conftest.run_with_devices` directly (no
``subproc`` fixture) so they stay in the ``-m "not slow"`` CI tier.
"""
import pytest

from conftest import run_with_devices

# Toy problem + side-by-side runner shared by every subprocess: params
# {"w": shapes[0], "b": shapes[1]} give an uneven flat payload (53 for
# the default (6,8)+(5,): not a multiple of n=16, so stripe rows are
# ragged), and the quadratic loss has dense, well-scaled gradients.
_COMMON = r'''
import jax, jax.numpy as jnp, numpy as np
from repro.dist.steps import (make_train_step, edst_spec_for_mesh,
                              fault_runtime_for_mesh, dp_size)
from repro.optim import AdamW, cosine_schedule, ShardedAdamW
from repro.launch.mesh import make_mesh

class QuadAPI:
    def loss_fn(self, params, batch):
        pred = jnp.einsum("bij,ij->b", batch["x"], params["w"]) \
            + batch["x2"] @ params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

def make_problem(shapes=((6, 8), (5,))):
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(*shapes[0]), jnp.float32) * 0.3,
              "b": jnp.asarray(rng.randn(*shapes[1]), jnp.float32) * 0.3}
    B = 32
    batch = {"x": jnp.asarray(rng.randn(B, *shapes[0]), jnp.float32),
             "x2": jnp.asarray(rng.randn(B, *shapes[1]), jnp.float32),
             "y": jnp.asarray(rng.randn(B), jnp.float32)}
    return QuadAPI(), params, batch

MESH_ARGS = ((16, 1), ("data", "model"))
TORUS = (4, 4)

def side_by_side(shapes=((6, 8), (5,)), steps=5, rtol_loss=1e-5,
                 rtol_g=1e-4, quantize=False, codec=None):
    """Train psum_dp and zero1 side by side; assert per-step agreement."""
    api, params, batch = make_problem(shapes)
    mesh = make_mesh(*MESH_ARGS)
    opt = AdamW(cosine_schedule(1e-2, 2, 20))
    spec = edst_spec_for_mesh(*MESH_ARGS, TORUS, engine="striped")
    ref = jax.jit(make_train_step(api, opt, mesh, mode="psum_dp"))
    z = jax.jit(make_train_step(api, opt, mesh, mode="edst", zero1=True,
                                engine="striped", dp_torus_shape=TORUS,
                                quantize=quantize, codec=codec))
    zstate = ShardedAdamW(opt).init_for(params, spec, dp_size(mesh))
    rstate = opt.init(params)
    rp = zp = params
    descended = []
    for s in range(steps):
        rp, rstate, rm = ref(rp, rstate, batch)
        zp, zstate, zm = z(zp, zstate, batch)
        rl, zl = float(rm["loss"]), float(zm["loss"])
        rg, zg = float(rm["grad_norm"]), float(zm["grad_norm"])
        assert abs(rl - zl) <= rtol_loss * abs(rl), (s, rl, zl)
        assert abs(rg - zg) <= rtol_g * max(rg, 1e-9), (s, rg, zg)
        descended.append(zl)
    assert descended[-1] < descended[0], descended
'''


def test_zero1_matches_psum_dp_under_link_kill():
    """f32 differential through the fault runtime: 3 healthy steps, a
    link kill (flip to the degraded class + re-shard mu/nu), 3 more
    steps -- loss/gnorm track psum_dp throughout and the schedule-id
    flip compiles nothing new."""
    run_with_devices(_COMMON + r'''
from repro.core.fault import FailureEvent

api, params, batch = make_problem()
mesh = make_mesh(*MESH_ARGS)
opt = AdamW(cosine_schedule(1e-2, 2, 20))
rt = fault_runtime_for_mesh(*MESH_ARGS, TORUS, engine="striped")
ref = jax.jit(make_train_step(api, opt, mesh, mode="psum_dp"))
z = jax.jit(make_train_step(api, opt, mesh, mode="edst", zero1=True,
                            fault_runtime=rt))
m = 53
zstate = ShardedAdamW(opt).init_for(params, rt, dp_size(mesh))
rstate = opt.init(params)
rp = zp = params
sid = jnp.int32(0)

def check(rm, zm, s):
    rl, zl = float(rm["loss"]), float(zm["loss"])
    rg, zg = float(rm["grad_norm"]), float(zm["grad_norm"])
    assert abs(rl - zl) <= 1e-5 * abs(rl), (s, rl, zl)
    assert abs(rg - zg) <= 1e-4 * max(rg, 1e-9), (s, rg, zg)

for s in range(3):
    rp, rstate, rm = ref(rp, rstate, batch)
    zp, zstate, zm = z(zp, zstate, batch, sid)
    check(rm, zm, s)
cache_before = z._cache_size()

# kill a link used by tree 0 of the full schedule -> degraded class
dead = next(iter(rt.entries[0].sched.trees[0].tree))
rt2 = rt.on_failure(FailureEvent(links=frozenset({dead})),
                    prefer="degraded")
assert rt2.active != rt.active
zstate = type(zstate)(zstate.step,
                      rt.reshard_owned(zstate.mu, 0, rt2.active, m),
                      rt.reshard_owned(zstate.nu, 0, rt2.active, m))
sid = jnp.int32(rt2.active)

for s in range(3, 6):
    rp, rstate, rm = ref(rp, rstate, batch)
    zp, zstate, zm = z(zp, zstate, batch, sid)
    check(rm, zm, s)
assert z._cache_size() == cache_before, (z._cache_size(), cache_before)
print("ZERO1 FAULT DIFF PASS")
''', 16)


def test_zero1_wave_count_contract():
    """The compiled zero1 step issues strictly fewer ppermute waves than
    the composed striped-allreduce step on the torus4x4 k=2 fabric:
    rs_waves + ag_waves < len(waves), asserted against the actual HLO
    with the phase-aware contract."""
    run_with_devices(_COMMON + r'''
from repro.analysis.verify import hlo_contract_for
from repro.analysis.hlo import lint_hlo

api, params, batch = make_problem()
mesh = make_mesh(*MESH_ARGS)
opt = AdamW(cosine_schedule(1e-2, 2, 20))
spec = edst_spec_for_mesh(*MESH_ARGS, TORUS, engine="striped")
z = make_train_step(api, opt, mesh, mode="edst", zero1=True,
                    engine="striped", dp_torus_shape=TORUS)
s = make_train_step(api, opt, mesh, mode="edst",
                    engine="striped", dp_torus_shape=TORUS)
m = 53
zst = ShardedAdamW(opt).init_for(params, spec, dp_size(mesh))
sst = opt.init(params)
ztxt = jax.jit(z).lower(params, zst, batch).compile().as_text()
stxt = jax.jit(s).lower(params, sst, batch).compile().as_text()
zc = hlo_contract_for(spec, m=m, phase="zero1")
sc = hlo_contract_for(spec, m=m, phase="composed")
assert lint_hlo(ztxt, zc) == [], lint_hlo(ztxt, zc)
assert lint_hlo(stxt, sc) == [], lint_hlo(stxt, sc)
assert zc.ppermutes < sc.ppermutes, (zc.ppermutes, sc.ppermutes)
print("WAVES", zc.ppermutes, "<", sc.ppermutes)
''', 16)


def test_zero1_q8_wire(subproc):
    """int8 gradient wire (codec="full"): the RS waves quantize, the
    params allgather stays f32, and the run still tracks psum_dp at the
    quantization-noise tolerance while descending."""
    subproc(_COMMON + r'''
side_by_side(quantize=True, codec="full", rtol_loss=1e-3, rtol_g=1e-2)
print("ZERO1 Q8 PASS")
''', 16)


def test_zero1_payload_smaller_than_fabric(subproc):
    """m = 7 < n = 16: most owner stripes are empty padding and whole
    waves drop out of the bound program; the differential claim must
    hold unchanged."""
    subproc(_COMMON + r'''
side_by_side(shapes=((2, 2), (3,)))
print("ZERO1 SMALL PASS")
''', 16)
