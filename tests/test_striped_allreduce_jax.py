"""Striped reduce-scatter/allgather engine under shard_map on 16 fake
host devices: striped_allreduce == psum == packet simulator (uneven m,
m < n, quantized wires, weighted fractions with a retired tree), the
first-class tree_reduce_scatter / tree_allgather ops against the numpy
stripe layout, spec-cache jit stability, and fault-runtime link kills on
an engine="striped" runtime."""

CODE = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    simulate_striped_program,
                                    striped_spec_from_schedule)
from repro.dist.striped import striped_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((16,), ('data',))


def smapped(body):
    return jax.shard_map(lambda xs: body(xs.reshape(xs.shape[1:]))[None],
                         mesh=mesh, in_specs=P('data'),
                         out_specs=P('data'))


for dims in [(4, 4), (2, 8)]:
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = striped_spec_from_schedule(sched, ('data',))

    # the packet replay validates the compiled program itself, with the
    # per-stripe conservation check on
    vals = np.random.RandomState(0).randn(sp.n, 8 * sched.k + 5)
    sim = simulate_striped_program(spec, vals)
    assert sim.ok and sim.stripes_ok, dims

    # uneven m (53 % k != 0), m < n (d=3): psum equivalence
    for d in (53, 3, 64):
        x = jnp.asarray(np.random.RandomState(d).randn(16, d)
                        .astype(np.float32))
        yp = jax.jit(smapped(lambda v: jax.lax.psum(v, 'data')))(x)
        y = jax.jit(smapped(lambda v: striped_allreduce(v, spec)))(x)
        assert jnp.allclose(y, yp, atol=1e-4), (dims, d)

        # quantized stripe wires (forced codecs -- "auto" may disable
        # compression on host backends): bounded relative error
        expect = x.sum(0)
        for codec in ("full", "hybrid", "bcast"):
            yq = jax.jit(smapped(
                lambda v, c=codec: striped_allreduce(
                    v, spec, quantize=True, codec=c)))(x)
            rel = float(jnp.max(jnp.abs(yq[0] - expect)
                                / (jnp.abs(expect) + 1)))
            assert rel < 0.35, (dims, d, codec, rel)
        # the model-picked codec stays psum-close on every backend
        ya = jax.jit(smapped(lambda v: striped_allreduce(
            v, spec, quantize=True)))(x)
        rel = float(jnp.max(jnp.abs(ya[0] - expect)
                            / (jnp.abs(expect) + 1)))
        assert rel < 0.35, (dims, d, rel)

    # weighted fractions, including a retired (fraction-0) tree
    if sched.k >= 2:
        x = jnp.asarray(np.random.RandomState(7).randn(16, 53)
                        .astype(np.float32))
        yp = jax.jit(smapped(lambda v: jax.lax.psum(v, 'data')))(x)
        for fr in [(0.7, 0.3), (1.0, 0.0)]:
            y = jax.jit(smapped(
                lambda v, fr=fr: striped_allreduce(
                    v, spec, fractions=fr)))(x)
            assert jnp.allclose(y, yp, atol=1e-4), (dims, fr)
            assert simulate_striped_program(
                spec, np.random.RandomState(1).randn(16, 53), fr).ok

print("STRIPED_ALLREDUCE_OK")
"""

RS_AG_CODE = r"""
import functools
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    striped_spec_from_schedule)
from repro.dist.striped import (stripe_layout, striped_allreduce,
                                tree_allgather, tree_reduce_scatter)
from repro.launch.mesh import make_mesh

mesh = make_mesh((16,), ('data',))
sp = topo.device_topology((4, 4))
sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
spec = striped_spec_from_schedule(sched, ('data',))

d = 37
x = jnp.asarray(np.random.RandomState(11).randn(16, d).astype(np.float32))
lay = stripe_layout(spec, d)

owned = jax.jit(jax.shard_map(
    lambda xs: tree_reduce_scatter(xs.reshape(xs.shape[1:]), spec)[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data')))(x)

# every vertex holds the globally-summed stripe its preorder slot owns
tot = np.asarray(x).sum(0)
off = 0
for j, s in enumerate(lay.sizes):
    chunk = np.zeros(lay.mrow, np.float32)
    chunk[:s] = tot[off:off + s]
    off += s
    for v in range(16):
        o = int(lay.own_off[j, v])
        l = int(lay.own_len[j, v])
        assert np.allclose(np.asarray(owned[v, j, :l]), chunk[o:o + l],
                           atol=1e-4), (j, v)
        assert np.allclose(np.asarray(owned[v, j, l:]), 0.0), (j, v)

# allgather is the exact inverse: every vertex reassembles the full sum
y = jax.jit(jax.shard_map(
    lambda ow: tree_allgather(ow.reshape(ow.shape[1:]), spec, (d,))[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data')))(owned)
assert jnp.allclose(y, jnp.tile(x.sum(0), (16, 1)), atol=1e-4)

# spec cache: recompiles return the identical object and never retrace
@functools.partial(jax.jit, static_argnums=1)
def run(xs, sp_):
    return jax.shard_map(
        lambda v: striped_allreduce(v.reshape(v.shape[1:]), sp_)[None],
        mesh=mesh, in_specs=P('data'), out_specs=P('data'))(xs)

s2 = striped_spec_from_schedule(
    allreduce_schedule(sp.n, star_edsts(sp).trees), ('data',))
assert s2 is spec, "spec cache must return the identical object"
y1 = run(x, spec)
before = run._cache_size()
y2 = run(x, s2)
assert run._cache_size() == before, "striped spec swap retraced"
assert jnp.allclose(y1, y2)
print("STRIPED_RS_AG_OK")
"""

FAULT_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.core.collectives import StripedCollectiveSpec
from repro.core.fault import FailureEvent
from repro.dist.steps import edst_spec_for_mesh, fault_runtime_for_mesh
from repro.dist.tree_allreduce import tree_allreduce
from repro.launch.mesh import make_mesh

# engine selection end to end: spec compile + generic dispatch
spec = edst_spec_for_mesh((16, 1), ('data', 'model'),
                          dp_torus_shape=(4, 4), engine="striped")
assert isinstance(spec, StripedCollectiveSpec)
assert edst_spec_for_mesh((16, 1), ('data', 'model'),
                          dp_torus_shape=(4, 4), engine="striped") is spec

rt = fault_runtime_for_mesh((16, 1), ('data', 'model'),
                            dp_torus_shape=(4, 4), engine="striped")
assert rt.engine == "striped"
assert all(isinstance(e.spec, StripedCollectiveSpec) for e in rt.entries)
mesh = make_mesh((16, 1), ('data', 'model'))
sync = rt.make_allreduce()

x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01

f = jax.jit(jax.shard_map(
    lambda xs, sid: sync(xs.reshape(xs.shape[1:]), sid)[None],
    mesh=mesh, in_specs=(P('data'), P()), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))
g = jax.jit(jax.shard_map(
    lambda xs: jax.lax.psum(xs.reshape(xs.shape[1:]), 'data')[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))
h = jax.jit(jax.shard_map(
    lambda xs: tree_allreduce(xs.reshape(xs.shape[1:]), spec)[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))

yp = g(x)
assert jnp.allclose(h(x), yp, atol=1e-4)     # dispatcher path
y0 = f(x, jnp.int32(0))

# kill a tree-0 link mid-run: scalar flip, no retrace, ownership
# re-stripes over the k-1 survivors, psum equality holds
dead = next(iter(rt.entries[0].sched.trees[0].tree))
rt2 = rt.on_failure(FailureEvent(links=frozenset({dead})))
traces = f._cache_size()
y1 = f(x, jnp.int32(rt2.active))
assert f._cache_size() == traces, "link-kill schedule flip retraced"
rt3 = rt.on_failure(FailureEvent(links=frozenset({dead})),
                    prefer="degraded")
assert rt3.entries[rt3.active].spec.k == rt.k - 1
y2 = f(x, jnp.int32(rt3.active))
for y in (y0, y1, y2):
    assert jnp.allclose(y, yp, atol=1e-2), float(jnp.max(jnp.abs(y - yp)))
print("STRIPED_FAULT_OK")
"""


def test_striped_matches_psum_and_simulator(subproc):
    out = subproc(CODE, 16)
    assert "STRIPED_ALLREDUCE_OK" in out


def test_reduce_scatter_allgather_first_class(subproc):
    out = subproc(RS_AG_CODE, 16)
    assert "STRIPED_RS_AG_OK" in out


def test_striped_fault_runtime_link_kill(subproc):
    out = subproc(FAULT_CODE, 16)
    assert "STRIPED_FAULT_OK" in out
