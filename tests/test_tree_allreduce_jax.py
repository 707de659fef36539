"""JAX shard_map EDST tree allreduce: numerical equivalence with psum, on
16 fake devices (subprocess so the main test process keeps 1 device)."""

CODE = r"""
import os
assert "XLA_FLAGS" in os.environ
import sys
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import allreduce_schedule
from repro.dist.tree_allreduce import spec_from_schedule, tree_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))
x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01
expect = x.sum(0)

for dims in [(4, 4), (2, 8)]:
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = spec_from_schedule(sched, ('a', 'b'))
    def f(xs):
        return tree_allreduce(xs.reshape(xs.shape[1:]), spec)[None]
    y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(('a','b')),
                              out_specs=P(('a','b'))))(x)
    assert jnp.allclose(y, jnp.tile(expect, (16, 1))), dims
    def fq(xs):
        return tree_allreduce(xs.reshape(xs.shape[1:]), spec, quantize=True)[None]
    yq = jax.jit(jax.shard_map(fq, mesh=mesh, in_specs=P(('a','b')),
                               out_specs=P(('a','b'))))(x)
    rel = float(jnp.max(jnp.abs(yq[0] - expect) / (jnp.abs(expect) + 1)))
    assert rel < 0.05, (dims, rel)
print("TREE_ALLREDUCE_OK")
"""

TRAIN_CODE = r"""
import os, jax, jax.numpy as jnp
from repro import configs
from repro.models.api import build
from repro.dist.steps import make_train_step
from repro.optim import AdamW, cosine_schedule
from repro.launch.mesh import make_mesh

cfg = configs.get('smollm-135m').reduced()
api = build(cfg)
mesh = make_mesh((4, 4), ('data', 'model'))
opt = AdamW(cosine_schedule(1e-3, 10, 100))
params, _ = api.init(jax.random.PRNGKey(0))
opt_state = opt.init(params)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab)}
outs = {}
for mode in ['gspmd', 'psum_dp', 'edst']:
    step = make_train_step(api, opt, mesh, mode=mode)
    with jax.set_mesh(mesh):
        p2, o2, m = jax.jit(step)(params, opt_state, batch)
    outs[mode] = (float(m['loss']), p2)
ref_loss, ref_p = outs['gspmd']
for mode in ['psum_dp', 'edst']:
    loss, p = outs[mode]
    assert abs(loss - ref_loss) < 1e-4, (mode, loss, ref_loss)
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(p)))
    assert diff < 1e-4, (mode, diff)
print("TRAIN_MODES_OK")
"""


def test_tree_allreduce_matches_sum(subproc):
    out = subproc(CODE, 16)
    assert "TREE_ALLREDUCE_OK" in out


def test_train_step_sync_modes_agree(subproc):
    out = subproc(TRAIN_CODE, 16)
    assert "TRAIN_MODES_OK" in out


DP_TORUS_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist.steps import edst_spec_for_mesh
from repro.dist.tree_allreduce import tree_allreduce
from repro.launch.mesh import make_mesh

# pure-DP pod: 16 devices on the 'data' axis, physically a 4x4 torus
mesh = make_mesh((16, 1), ('data', 'model'))
spec = edst_spec_for_mesh((16, 1), ('data', 'model'), dp_torus_shape=(4, 4))
assert spec.k == 2, spec.k   # the 2D torus gives the maximal 2 EDSTs
x = jnp.arange(16 * 19, dtype=jnp.float32).reshape(16, 19)
def f(xs):
    return tree_allreduce(xs.reshape(xs.shape[1:]), spec)[None]
y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P('data'),
                          out_specs=P('data'), axis_names={'data'},
                          check_vma=False))(x)
assert jnp.allclose(y, jnp.tile(x.sum(0), (16, 1)))
print("DP_TORUS_OK")
"""


def test_dp_torus_shape_override(subproc):
    out = subproc(DP_TORUS_CODE, 16)
    assert "DP_TORUS_OK" in out
