"""Pipelined segmented executor under shard_map on 16 fake host devices:
psum/simulator equivalence (quantize on/off, uneven m, m < S, weighted
fractions with a retired tree), scan-program jit-cache stability, the HLO
contract (one collective per wave, independent of the segment count),
fault-runtime link-kill equality on the pipelined engine, and the
segmented scan's exact equality with the unrolled program (on 4 or 16
devices)."""
import pytest

CODE = r"""
import os
assert "XLA_FLAGS" in os.environ
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    pipelined_spec_from_schedule,
                                    simulate_wave_program)
from repro.dist.tree_allreduce import pipelined_tree_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))


def smapped(body):
    return jax.shard_map(lambda xs: body(xs.reshape(xs.shape[1:]))[None],
                         mesh=mesh, in_specs=P(('a', 'b')),
                         out_specs=P(('a', 'b')))


for dims in [(4, 4), (2, 8)]:
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = pipelined_spec_from_schedule(sched, ('a', 'b'))

    # the packet-level replay validates the compiled wave program itself
    vals = np.random.RandomState(0).randn(sp.n, 8 * sched.k + 5)
    for S in (1, 2, 4, 8):
        for q in (False, True):
            sim = simulate_wave_program(spec, vals, segments=S, quantized=q)
            assert sim.ok, (dims, S, q)
            waves = spec.q8_waves if q else spec.waves
            assert sim.rounds == len(waves) + S - 1

    # uneven m (53 % k != 0) and m < S (d=3, S=8): psum equivalence
    for d in (53, 3):
        x = jnp.asarray(np.random.RandomState(d).randn(16, d)
                        .astype(np.float32))
        yp = jax.jit(smapped(lambda v: jax.lax.psum(v, ('a', 'b'))))(x)
        for S in (1, 2, 8, "auto"):
            y = jax.jit(smapped(lambda v, S=S: pipelined_tree_allreduce(
                v, spec, segments=S)))(x)
            assert jnp.allclose(y, yp, atol=1e-4), (dims, d, S)

        # quantized wires (forced codecs -- "auto" may disable
        # compression on host backends): bounded relative error
        expect = x.sum(0)
        for codec in ("full", "hybrid", "bcast"):
            for S in (1, 4):
                yq = jax.jit(smapped(
                    lambda v, c=codec, S=S: pipelined_tree_allreduce(
                        v, spec, quantize=True, segments=S, codec=c)))(x)
                rel = float(jnp.max(jnp.abs(yq[0] - expect)
                                    / (jnp.abs(expect) + 1)))
                assert rel < 0.35, (dims, d, codec, S, rel)
        # the model-picked codec stays psum-close on every backend
        ya = jax.jit(smapped(lambda v: pipelined_tree_allreduce(
            v, spec, quantize=True)))(x)
        rel = float(jnp.max(jnp.abs(ya[0] - expect)
                            / (jnp.abs(expect) + 1)))
        assert rel < 0.35, (dims, d, rel)

    # weighted fractions, including a retired (fraction-0) tree
    if sched.k >= 2:
        x = jnp.asarray(np.random.RandomState(7).randn(16, 53)
                        .astype(np.float32))
        yp = jax.jit(smapped(lambda v: jax.lax.psum(v, ('a', 'b'))))(x)
        for fr in [(0.7, 0.3), (1.0, 0.0)]:
            for S in (1, 4):
                y = jax.jit(smapped(
                    lambda v, fr=fr, S=S: pipelined_tree_allreduce(
                        v, spec, segments=S, fractions=fr)))(x)
                assert jnp.allclose(y, yp, atol=1e-4), (dims, fr, S)

print("PIPELINED_ALLREDUCE_OK")
"""

HLO_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.analysis.hlo import lint_hlo
from repro.analysis.verify import hlo_contract_for
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    pipelined_spec_from_schedule)
from repro.dist.tree_allreduce import pipelined_tree_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))
x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01


def smapped(body):
    return jax.shard_map(lambda xs: body(xs.reshape(xs.shape[1:]))[None],
                         mesh=mesh, in_specs=P(('a', 'b')),
                         out_specs=P(('a', 'b')))


def hlo_text(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


for dims in [(4, 4), (2, 8)]:
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    spec = pipelined_spec_from_schedule(sched, ('a', 'b'))

    # the pipeline runs waves + S - 1 steps
    for S in (1, 2, 8):
        assert spec.steps(S) == len(spec.waves) + S - 1

    # S=1 unrolls, S>1 scans: either way the HLO holds each wave's
    # collective exactly ONCE -- program size flat in the segment count
    # (the whole point of the scan compile).  The contract is derived
    # from the spec itself (hlo_contract_for) and enforced by lint_hlo.
    contract = hlo_contract_for(spec)
    assert contract.ppermutes == len(spec.waves)
    for S in (1, 2, 8):
        text = hlo_text(smapped(
            lambda v, S=S: pipelined_tree_allreduce(v, spec, segments=S)), x)
        bad = lint_hlo(text, contract)
        assert not bad, (dims, S, bad)

    # quantized S=1: one collective per q8 wave, int8 reduce wires -- f32
    # sites only on the packed broadcast waves, and every f32 wire is the
    # packed lane width, never a full mrow-element row (a full row means
    # the codec was silently dropped)
    qcontract = hlo_contract_for(spec, quantize=True, m=53)
    assert qcontract.ppermutes == len(spec.q8_waves)
    text = hlo_text(smapped(
        lambda v: pipelined_tree_allreduce(v, spec, quantize=True,
                                           segments=1, codec="full")), x)
    bad = lint_hlo(text, qcontract)
    assert not bad, (dims, bad)

print("PIPELINED_HLO_OK")
"""

CACHE_CODE = r"""
import functools
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    pipelined_spec_from_schedule)
from repro.dist.tree_allreduce import pipelined_tree_allreduce
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 4), ('a', 'b'))
x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01


@functools.partial(jax.jit, static_argnums=(1, 2))
def run(xs, spec, segments):
    return jax.shard_map(
        lambda v: pipelined_tree_allreduce(v.reshape(v.shape[1:]), spec,
                                           segments=segments)[None],
        mesh=mesh, in_specs=P(('a', 'b')), out_specs=P(('a', 'b')))(xs)


def fresh_spec():
    sp = topo.device_topology((4, 4))
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    return pipelined_spec_from_schedule(sched, ('a', 'b'))


s1, s2 = fresh_spec(), fresh_spec()
assert s1 is s2, "spec cache must return the identical object"
for segments in (1, 4):   # both the unrolled and the scan program
    y1 = run(x, s1, segments)
    before = run._cache_size()
    y2 = run(x, s2, segments)
    assert run._cache_size() == before, \
        f"pipelined spec swap retraced (segments={segments})"
    assert jnp.allclose(y1, y2)
    assert jnp.allclose(y1, jnp.tile(x.sum(0), (16, 1)))
print("PIPELINED_CACHE_OK")
"""

FAULT_CODE = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import repro.dist
from repro.core.collectives import PipelinedAllreduceSpec
from repro.core.fault import FailureEvent
from repro.dist.steps import fault_runtime_for_mesh
from repro.launch.mesh import make_mesh

rt = fault_runtime_for_mesh((16, 1), ('data', 'model'), dp_torus_shape=(4, 4))
# the elastic runtime's precompiled programs are pipelined specs now
assert all(isinstance(e.spec, PipelinedAllreduceSpec) for e in rt.entries)
mesh = make_mesh((16, 1), ('data', 'model'))
sync = rt.make_allreduce(quantize=True, segments=2)  # scan path in-switch

x = jnp.arange(16 * 53, dtype=jnp.float32).reshape(16, 53) * 0.01

f = jax.jit(jax.shard_map(
    lambda xs, sid: sync(xs.reshape(xs.shape[1:]), sid)[None],
    mesh=mesh, in_specs=(P('data'), P()), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))
g = jax.jit(jax.shard_map(
    lambda xs: jax.lax.psum(xs.reshape(xs.shape[1:]), 'data')[None],
    mesh=mesh, in_specs=P('data'), out_specs=P('data'),
    axis_names={'data'}, check_vma=False))

yp = g(x)
y0 = f(x, jnp.int32(0))

# kill a tree-0 link mid-run: scalar flip, no retrace, psum equality holds
dead = next(iter(rt.entries[0].sched.trees[0].tree))
rt2 = rt.on_failure(FailureEvent(links=frozenset({dead})))
traces = f._cache_size()
y1 = f(x, jnp.int32(rt2.active))
assert f._cache_size() == traces, "link-kill schedule flip retraced"
rt3 = rt.on_failure(FailureEvent(links=frozenset({dead})),
                    prefer="degraded")
y2 = f(x, jnp.int32(rt3.active))
for y in (y0, y1, y2):
    assert jnp.allclose(y, yp, atol=1e-2), float(jnp.max(jnp.abs(y - yp)))
print("PIPELINED_FAULT_OK")
"""

# run with ``DIMS = (rows, cols)`` prepended: the fabric and its mesh
EXACT_CODE = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import topologies as topo
from repro.core.edst_star import star_edsts
from repro.core.collectives import (allreduce_schedule,
                                    pipelined_spec_from_schedule)
from repro.dist.tree_allreduce import pipelined_tree_allreduce
from repro.launch.mesh import make_mesh

n = DIMS[0] * DIMS[1]
mesh = make_mesh(DIMS, ('a', 'b'))
sp = topo.device_topology(DIMS)
sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
spec = pipelined_spec_from_schedule(sched, ('a', 'b'))


def run(x, **kw):
    return jax.jit(jax.shard_map(
        lambda xs: pipelined_tree_allreduce(xs.reshape(xs.shape[1:]), spec,
                                            **kw)[None],
        mesh=mesh, in_specs=P(('a', 'b')), out_specs=P(('a', 'b'))))(x)


# the scan reorders no arithmetic: every element takes the same adds
# as the S=1 unrolled program, so the f32 results are equal (``==``
# holds for zeros of either sign)
fractions = [None] + ([(0.7, 0.3), (1.0, 0.0)] if sched.k >= 2 else [])
for d in (53, 3):          # uneven m; m smaller than S
    x = jnp.asarray(np.random.RandomState(d).randn(n, d).astype(np.float32))
    for fr in fractions:
        y1 = run(x, segments=1, fractions=fr)
        for S in (2, 4, 8, 64):
            y = run(x, segments=S, fractions=fr)
            assert bool(jnp.all(y == y1)), (DIMS, d, fr, S)
    expect = x.sum(0)
    for codec in ("full", "hybrid", "bcast"):
        yq = run(x, quantize=True, segments=64, codec=codec)
        rel = float(jnp.max(jnp.abs(yq[0] - expect) / (jnp.abs(expect) + 1)))
        assert rel < 0.35, (DIMS, d, codec, rel)
print("PIPELINED_EXACT_OK", sched.k)
"""


def test_pipelined_matches_psum_and_simulator(subproc):
    out = subproc(CODE, 16)
    assert "PIPELINED_ALLREDUCE_OK" in out


def test_pipelined_hlo_contract_flat_in_segments(subproc):
    out = subproc(HLO_CODE, 16)
    assert "PIPELINED_HLO_OK" in out


def test_pipelined_scan_program_jit_cache_stable(subproc):
    out = subproc(CACHE_CODE, 16)
    assert "PIPELINED_CACHE_OK" in out


def test_pipelined_fault_runtime_link_kill(subproc):
    out = subproc(FAULT_CODE, 16)
    assert "PIPELINED_FAULT_OK" in out


@pytest.mark.parametrize("dims", [(4, 1), (4, 4), (2, 8)],
                         ids=lambda d: f"{d[0]}x{d[1]}")
def test_pipelined_scan_equals_unrolled(subproc, dims):
    """The segmented scan (segment-major carry, every wave's read before
    any write) returns exactly the S=1 unrolled program's f32 sums."""
    out = subproc(f"DIMS = {dims!r}\n" + EXACT_CODE, dims[0] * dims[1])
    assert "PIPELINED_EXACT_OK" in out
