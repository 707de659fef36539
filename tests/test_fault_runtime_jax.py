"""Elastic EDST runtime under shard_map on 16 fake host devices: killing a
tree's link mid-run flips a scalar schedule id (no retrace) and keeps the
edst gradient sync numerically equal to ``jax.lax.psum``."""

ALLREDUCE_CODE = r"""
import os
assert "XLA_FLAGS" in os.environ
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.fault import FailureEvent
from repro.dist.steps import fault_runtime_for_mesh
from repro.launch.mesh import make_mesh

rt = fault_runtime_for_mesh((16, 1), ('data', 'model'), dp_torus_shape=(4, 4))
assert rt.k == 2 and len(rt.entries) == 5
mesh = make_mesh((16, 1), ('data', 'model'))
sync = rt.make_allreduce()

x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01
expect = x.sum(0)

def body(xs, sid):
    return sync(xs.reshape(xs.shape[1:]), sid)[None]

f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P('data'), P()),
                          out_specs=P('data'), axis_names={'data'},
                          check_vma=False))

# healthy run, then kill a tree-0 link mid-run: same compiled fn, new id
y0 = f(x, jnp.int32(0))
assert jnp.allclose(y0, jnp.tile(expect, (16, 1)))

dead = next(iter(rt.entries[0].sched.trees[0].tree))
rt2 = rt.on_failure(FailureEvent(links=frozenset({dead})))
assert rt2.active != 0 and rt2.entries is rt.entries
traces_before = f._cache_size()
y1 = f(x, jnp.int32(rt2.active))             # schedule flip: no retrace
assert f._cache_size() == traces_before, "schedule switch retraced"
assert jnp.allclose(y1, jnp.tile(expect, (16, 1)))

# the degraded (k-1 striping) program agrees too
rt3 = rt.on_failure(FailureEvent(links=frozenset({dead})), prefer="degraded")
assert rt3.entry.name == "degraded/tree0" and rt3.entry.k == 1
y2 = f(x, jnp.int32(rt3.active))
assert jnp.allclose(y2, jnp.tile(expect, (16, 1)))

# equality with psum on the same mesh
g = jax.jit(jax.shard_map(
    lambda xs: jax.lax.psum(xs.reshape(xs.shape[1:]), 'data')[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))
yp = g(x)
for y in (y0, y1, y2):
    assert jnp.allclose(y, yp, atol=1e-5)
print("FAULT_ALLREDUCE_OK")
"""

TRAIN_CODE = r"""
import jax, jax.numpy as jnp
from repro import configs
from repro.core.fault import FailureEvent
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

ref_step = make_train_step(api, opt, mesh, mode='psum_dp')
step = make_train_step(api, opt, mesh, mode='edst', fault_runtime=rt)

with jax.set_mesh(mesh):
    jstep = jax.jit(step)
    jref = jax.jit(ref_step)
    # step 1: healthy schedule
    p1, o1, m1 = jstep(params, opt_state, batch, jnp.int32(0))
    r1, ro1, rm1 = jref(params, opt_state, batch)
    # mid-run link failure: flip the schedule id, keep the compiled step
    dead = next(iter(rt.entries[0].sched.trees[0].tree))
    rt = rt.on_failure(FailureEvent(links=frozenset({dead})),
                       prefer="degraded")
    p2, o2, m2 = jstep(p1, o1, batch, jnp.int32(rt.active))
    r2, ro2, rm2 = jref(r1, ro1, batch)

for (ma, mb) in ((m1, rm1), (m2, rm2)):
    assert abs(float(ma['loss']) - float(mb['loss'])) < 1e-4
diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))
           for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(r2)))
assert diff < 1e-4, diff
print("FAULT_TRAIN_OK")
"""


def test_fault_allreduce_survives_link_kill(subproc):
    out = subproc(ALLREDUCE_CODE, 16)
    assert "FAULT_ALLREDUCE_OK" in out


def test_fault_train_step_matches_psum_after_failure(subproc):
    out = subproc(TRAIN_CODE, 16)
    assert "FAULT_TRAIN_OK" in out
