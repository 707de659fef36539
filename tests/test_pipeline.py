"""GPipe stage runner == sequential stage application (4 fake devices)."""

CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist.pipeline import pipeline_apply
from repro.launch.mesh import make_mesh

n_stages, n_micro, mb, d = 4, 8, 2, 16
mesh = make_mesh((n_stages,), ('stage',))
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

def stage_fn(w, h):
    return jnp.tanh(h @ w[0])

def pipelined(ws, x):
    return pipeline_apply(stage_fn, ws, x, 'stage')

y = jax.jit(jax.shard_map(pipelined, mesh=mesh,
                          in_specs=(P('stage'), P()),
                          out_specs=P(), check_vma=False))(ws, x)
# sequential reference
ref = x
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s])
assert jnp.allclose(y, ref, atol=1e-5), float(jnp.max(jnp.abs(y - ref)))
from repro.dist.pipeline import bubble_fraction
assert abs(bubble_fraction(8, 4) - 3/11) < 1e-9
print("PIPELINE_OK")
"""


def test_pipeline_matches_sequential(subproc):
    out = subproc(CODE, 4)
    assert "PIPELINE_OK" in out
