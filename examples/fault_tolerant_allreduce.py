"""Elastic EDST allreduce: kill links mid-run, keep the compiled step.

Drives :mod:`repro.dist.fault` end to end on 16 fake host devices (a 4x4
torus DP fabric):
  1. build the elastic runtime: ONE compile covers the healthy k-tree
     schedule plus every degraded/rebuilt failure-class program;
  2. run the jitted allreduce healthy, then fail a tree-0 link: recovery is
     a scalar schedule-id flip into the SAME compiled executable (no
     retrace), verified numerically against the plain sum;
  3. compare the immediate degraded program (k-1 striping, ~1/k bandwidth
     lost) with the precompiled Roskind-Tarjan rebuilt program;
  4. a multi-tree failure escapes the precompiled classes ->
     ``with_rebuild`` repacks the actual residual fabric (one new compile);
  5. straggler mitigation stays schedule-level: ``rebalance_chunks``
     re-stripes chunk fractions around a slow chip.

Expected output (exact ids/links can shift with the EDST construction):

    elastic runtime: n=16 fabric, k=2 trees, 5 precompiled programs
      id 0: full            k=2 depth=10   48.1 GB/s
      id 1: degraded/tree0  k=1 depth=10   24.5 GB/s
      ...
    healthy allreduce correct: True (schedule id 0)
    *** link failure (4, 8) -> schedule id flips, no retrace ***
    recovery program rebuilt/tree0: k=1, correct: True
    bandwidth: healthy 48.1 GB/s -> degraded 24.5 GB/s -> rebuilt 24.5 GB/s
    *** multi-tree failure -> dynamic rebuild ***
    with_rebuild: k=1 on the residual fabric, sim correct: True
    *** straggler: chip 5 running 8x slow ***
    re-striped chunk fractions: [...]

    PYTHONPATH=src python examples/fault_tolerant_allreduce.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402
from jax.sharding import PartitionSpec as P                       # noqa: E402

import repro.dist                                                 # noqa: E402
from repro.core.fault import FailureEvent, rebalance_chunks       # noqa: E402
from repro.dist.fault import NoScheduleError                      # noqa: E402
from repro.dist.steps import fault_runtime_for_mesh               # noqa: E402
from repro.launch.mesh import make_mesh                           # noqa: E402

# 1. the elastic runtime: all failure-class programs precompiled ------------
rt = fault_runtime_for_mesh((16, 1), ("data", "model"), dp_torus_shape=(4, 4))
report = rt.report(nbytes=64 << 20)
print(f"elastic runtime: n={report['n']} fabric, k={report['k']} trees, "
      f"{len(report['entries'])} precompiled programs")
for row in report["entries"]:
    print(f"  id {row['id']}: {row['name']:15s} k={row['k']} "
          f"depth={row['depth']:<3d} {row['gbps']:5.1f} GB/s")

# 2. jitted switch: healthy run, then a link failure mid-run ----------------
mesh = make_mesh((16, 1), ("data", "model"))
sync = rt.make_allreduce()
x = jnp.arange(16 * 37, dtype=jnp.float32).reshape(16, 37) * 0.01
expect = jnp.tile(x.sum(0), (16, 1))

f = jax.jit(jax.shard_map(
    lambda xs, sid: sync(xs.reshape(xs.shape[1:]), sid)[None],
    mesh=mesh, in_specs=(P("data"), P()), out_specs=P("data"),
    axis_names={"data"}, check_vma=False))

y = f(x, jnp.int32(0))
print(f"\nhealthy allreduce correct: {bool(jnp.allclose(y, expect))} "
      f"(schedule id 0)")

dead = next(iter(rt.entries[0].sched.trees[0].tree))
print(f"\n*** link failure {dead} -> schedule id flips, no retrace ***")
rt_fail = rt.on_failure(FailureEvent(links=frozenset({dead})))
y2 = f(x, jnp.int32(rt_fail.active))      # same executable, new scalar
print(f"recovery program {rt_fail.entry.name}: k={rt_fail.entry.k}, "
      f"correct: {bool(jnp.allclose(y2, expect))}")

# 3. degraded vs rebuilt bandwidth ------------------------------------------
nb = 64 << 20
deg = rt.on_failure(FailureEvent(links=frozenset({dead})), prefer="degraded")
print(f"bandwidth: healthy {rt.effective_bandwidth(nb, 0) / 1e9:.1f} GB/s -> "
      f"degraded {deg.effective_bandwidth(nb) / 1e9:.1f} GB/s -> "
      f"rebuilt {rt_fail.effective_bandwidth(nb) / 1e9:.1f} GB/s")

# 4. beyond the precompiled classes: dynamic rebuild ------------------------
print("\n*** multi-tree failure -> dynamic rebuild ***")
multi = FailureEvent(links=frozenset(
    next(iter(e.sched.trees[0].tree)) for e in rt.entries))
try:
    rt.on_failure(multi)
    print("unexpected: a precompiled program survived")
except NoScheduleError:
    rt_dyn = rt.with_rebuild(multi)
    print(f"with_rebuild: k={rt_dyn.k} on the residual fabric, "
          f"sim correct: {rt_dyn.verify_entry(0)}")

# 5. straggler mitigation (schedule-level, from core.fault) -----------------
print("\n*** straggler: chip 5 running 8x slow ***")
fracs = rebalance_chunks(rt.entries[0].sched, {5: 8.0})
print("re-striped chunk fractions:", [round(fr, 3) for fr in fracs])
