"""Fused global-round executor under shard_map on 16 fake host devices:
psum/simulator equivalence (quantize on/off), jit-cache stability across
spec recompiles, and the HLO collective-count contract (depth-of-deepest-
tree waves, one collective per quantized hop)."""

CODE = r"""
import os
assert "XLA_FLAGS" in os.environ
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    fused_spec_from_schedule,
                                    simulate_allreduce)
from repro.dist.tree_allreduce import (fused_tree_allreduce,
                                       per_tree_allreduce,
                                       spec_from_schedule)
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))
x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01
expect = x.sum(0)


import re

def hlo_collectives(f, *args):
    # op position only ("%x = f32[...] collective-permute(...)"), not
    # fusion metadata that mentions the op name; async start/done pairs
    # count once via -start
    text = jax.jit(f).lower(*args).compile().as_text()
    return sum(1 for l in text.splitlines()
               if re.search(r"=\s+\S+\s+collective-permute(-start)?\(", l))


def smapped(body):
    return jax.shard_map(lambda xs: body(xs.reshape(xs.shape[1:]))[None],
                         mesh=mesh, in_specs=P(('a', 'b')),
                         out_specs=P(('a', 'b')))

for dims in [(4, 4), (2, 8)]:
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    fspec = fused_spec_from_schedule(sched, ('a', 'b'))
    lspec = spec_from_schedule(sched, ('a', 'b'))

    # the packet-level simulator accepts the same schedule
    vals = np.random.RandomState(0).randn(sp.n, 8 * sched.k)
    assert simulate_allreduce(sched, vals).ok

    # psum equivalence, quantize off/on
    yp = jax.jit(smapped(lambda v: jax.lax.psum(v, ('a', 'b'))))(x)
    y = jax.jit(smapped(lambda v: fused_tree_allreduce(v, fspec)))(x)
    assert jnp.allclose(y, yp, atol=1e-5), dims
    assert jnp.allclose(y, jnp.tile(expect, (16, 1))), dims
    yq = jax.jit(smapped(
        lambda v: fused_tree_allreduce(v, fspec, quantize=True)))(x)
    rel = float(jnp.max(jnp.abs(yq[0] - expect) / (jnp.abs(expect) + 1)))
    assert rel < 0.05, (dims, rel)

    # HLO contract: one collective per wave -- depth-of-deepest-tree
    # global rounds, NOT sum-of-all-trees rounds; quantization must not
    # add a second collective per hop (the scale rides the payload tail)
    legacy_rounds = sum(len(t.reduce_rounds) + len(t.bcast_rounds)
                        for t in lspec.trees)
    n_fused = hlo_collectives(smapped(
        lambda v: fused_tree_allreduce(v, fspec)), x)
    n_fused_q = hlo_collectives(smapped(
        lambda v: fused_tree_allreduce(v, fspec, quantize=True)), x)
    n_legacy = hlo_collectives(smapped(
        lambda v: per_tree_allreduce(v, lspec)), x)
    n_legacy_q = hlo_collectives(smapped(
        lambda v: per_tree_allreduce(v, lspec, quantize=True)), x)
    assert n_fused == fspec.num_collectives, (dims, n_fused)
    assert n_fused_q == fspec.num_collectives, (dims, n_fused_q)
    assert n_legacy == n_legacy_q == legacy_rounds, (dims, n_legacy)
    if sched.k >= 2:
        assert n_fused < n_legacy, (dims, n_fused, n_legacy)

print("FUSED_ALLREDUCE_OK")
"""

CACHE_CODE = r"""
import functools
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    fused_spec_from_schedule)
from repro.dist.tree_allreduce import fused_tree_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))
x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01

@functools.partial(jax.jit, static_argnums=(1,))
def run(xs, spec):
    return jax.shard_map(
        lambda v: fused_tree_allreduce(v.reshape(v.shape[1:]), spec)[None],
        mesh=mesh, in_specs=P(('a', 'b')), out_specs=P(('a', 'b')))(xs)

def fresh_spec():
    sp = topo.device_topology((4, 4))
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    return fused_spec_from_schedule(sched, ('a', 'b'))

s1, s2 = fresh_spec(), fresh_spec()
assert s1 is s2, "spec cache must return the identical object"
y1 = run(x, s1)
before = run._cache_size()
y2 = run(x, s2)
assert run._cache_size() == before, "fused spec swap retraced"
assert jnp.allclose(y1, y2)
assert jnp.allclose(y1, jnp.tile(x.sum(0), (16, 1)))
print("FUSED_CACHE_OK")
"""


def test_fused_allreduce_matches_psum_and_hlo_contract(subproc):
    out = subproc(CODE, 16)
    assert "FUSED_ALLREDUCE_OK" in out


def test_fused_spec_swap_does_not_retrace(subproc):
    out = subproc(CACHE_CODE, 16)
    assert "FUSED_CACHE_OK" in out
