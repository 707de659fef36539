"""Measured wave timing: the wave-by-wave instrumented executor.

The production executors run a compiled program's waves inside one
``jit``; XLA is free to fuse and overlap, so end-to-end wall clock says
nothing about *which* waves dominate.  This module re-runs the SAME wave
bodies (the pipelined engine's ``_select_payload``/``_apply_wave`` pair,
the striped engine's ``_run_wave``) one jitted step per wave with
``block_until_ready`` between steps, yielding per-wave durations to set
against the :class:`repro.core.collectives.CostModel`'s per-wave
predictions (``CostModel.wave_times``).  Residuals land in
``BENCH_telemetry.json`` via :mod:`benchmarks.telemetry_bench`, and
:func:`register_measured` feeds the fitted ``alpha``/``link_bw`` back
into the measured-calibration registry
(``CostModel.register_calibration``).

Serializing waves adds dispatch overhead the fused program doesn't pay,
so measured *totals* here upper-bound the production path; the per-wave
*shape* (which waves are wide, where alpha dominates) is the datapoint.
For attribution inside the production path itself, the executors label
every wave with ``jax.named_scope("edst/t{tree}/w{wave}/{op}")`` (see
``tree_allreduce.set_wave_scopes``), so an XLA device profile taken with
``jax.profiler.trace`` groups per-op time by wave with zero runtime
cost.

JAX imports are function-local: importing this module is safe without an
accelerator runtime, and calling :func:`ensure_devices` FIRST (before
anything imports jax) forces enough fake host devices for the spec.
"""
from __future__ import annotations

import os
import sys
import time

from ..core.collectives import (CostModel, PipelinedAllreduceSpec,
                                StripedCollectiveSpec, chunk_sizes,
                                striped_tables, wave_wire_bytes)

DEFAULT_NBYTES = 4 << 20
DEFAULT_ITERS = 5


def ensure_devices(n: int) -> None:
    """Force >= ``n`` fake host devices; must run BEFORE jax initializes
    its backend (no-op once jax is imported -- the later device-count
    check raises with instructions instead)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _mesh_for(spec):
    import jax
    if jax.device_count() < spec.n:
        raise RuntimeError(
            f"spec needs {spec.n} devices, backend has "
            f"{jax.device_count()}; call telemetry.timing.ensure_devices"
            f"({spec.n}) (or set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={spec.n}) before anything imports jax")
    from ..launch.mesh import make_mesh
    return make_mesh((spec.n,), (spec.axes[0],))


def _jit_wave(step, mesh, nstate: int):
    """jit(shard_map(...)) around one wave body over ``nstate`` state
    arrays, each carried with a leading sharded device axis."""
    import jax
    from jax.sharding import PartitionSpec as P
    spec_in = (P(mesh.axis_names[0]),) * nstate

    def outer(*arrs):
        out = step(tuple(a.reshape(a.shape[1:]) for a in arrs))
        return tuple(a[None] for a in out)

    sm = jax.shard_map(outer, mesh=mesh, in_specs=spec_in,
                       out_specs=spec_in)
    return jax.jit(lambda state: sm(*state))


def _pipelined_steps(spec, mesh, nbytes: int, fractions):
    """(initial state, per-wave jitted step fns) for the pipelined
    engine's S=1 wave program: state is the tuple of k chunk rows."""
    import jax
    import jax.numpy as jnp
    from ..dist import tree_allreduce as ta
    axis = spec.axes[0]
    elems = max(1, -(-int(nbytes) // 4))
    k = spec.k
    if fractions is None:
        mrow = -(-elems // k)
        sizes = (mrow,) * k
    else:
        sizes = chunk_sizes(elems, tuple(fractions))
        mrow = max(sizes)

    def prep(arrs):
        return tuple(ta._rows_of(arrs[0].reshape(-1), k, sizes, mrow))

    def wave_step(wv):
        def step(rows, wv=wv):
            idx = jax.lax.axis_index(axis)
            recv = jax.lax.ppermute(
                ta._select_payload(list(rows), wv, idx), axis,
                list(wv.perm))
            return tuple(ta._apply_wave(list(rows), wv, recv, idx))
        return step

    x = (jnp.arange(spec.n * elems, dtype=jnp.float32)
         .reshape(spec.n, elems) * 1e-4)
    prep_in = (jax.shard_map(
        lambda a: tuple(r[None] for r in prep((a.reshape(a.shape[1:]),))),
        mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(mesh.axis_names[0]),
        out_specs=(jax.sharding.PartitionSpec(mesh.axis_names[0]),) * k))
    state = jax.jit(prep_in)(x)
    fns = [_jit_wave(wave_step(wv), mesh, k) for wv in spec.waves]
    return state, fns


def _striped_steps(spec, mesh, nbytes: int, fractions):
    """(initial state, per-wave jitted step fns) for the striped
    engine's composed RS/AG program: state is the (k, mrow) row stack."""
    import jax
    import jax.numpy as jnp
    from ..dist import striped as sd
    axis = spec.axes[0]
    elems = max(1, -(-int(nbytes) // 4))
    fr = None if fractions is None else tuple(fractions)
    bound = striped_tables(spec, elems, fr)

    def wave_step(bw):
        def step(arrs, bw=bw):
            idx = jax.lax.axis_index(axis)
            return (sd._run_wave(arrs[0], bw, idx, axis, None, None),)
        return step

    x = (jnp.arange(spec.n * elems, dtype=jnp.float32)
         .reshape(spec.n, elems) * 1e-4)
    P = jax.sharding.PartitionSpec
    prep_in = jax.shard_map(
        lambda a: sd._rows_in(a.reshape(a.shape[1:]).reshape(-1),
                              bound.sizes, bound.mrow)[None],
        mesh=mesh, in_specs=P(mesh.axis_names[0]),
        out_specs=P(mesh.axis_names[0]))
    state = (jax.jit(prep_in)(x),)
    fns = [_jit_wave(wave_step(bw), mesh, 1) for bw in bound.waves]
    return state, fns


def measured_wave_times(spec, nbytes: int = DEFAULT_NBYTES,
                        iters: int = DEFAULT_ITERS, fractions=None,
                        mesh=None) -> tuple:
    """Best-of-``iters`` measured seconds per wave of the compiled
    program, executed wave-by-wave on real (or fake-host) devices with a
    ``block_until_ready`` barrier per wave.  Every wave is timed against
    its true input state (states are propagated through the program
    first, which also compiles every step)."""
    ensure_devices(spec.n)
    import jax
    if isinstance(spec, StripedCollectiveSpec):
        builder = _striped_steps
    elif isinstance(spec, PipelinedAllreduceSpec):
        builder = _pipelined_steps
    else:
        raise NotImplementedError(
            "wave-by-wave timing instruments the production engines "
            "(pipelined, striped); use the named-scope profiler path for "
            "the fused/per-tree baselines")
    mesh = mesh or _mesh_for(spec)
    state, fns = builder(spec, mesh, nbytes, fractions)

    states = [state]
    for fn in fns:                      # compile + propagate true inputs
        state = fn(state)
        states.append(state)
    jax.block_until_ready(states[-1])

    best = [float("inf")] * len(fns)
    for _ in range(max(1, iters)):
        for w, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(states[w]))
            best[w] = min(best[w], time.perf_counter() - t0)
    return tuple(best)


def wave_report(spec, nbytes: int = DEFAULT_NBYTES,
                iters: int = DEFAULT_ITERS, fractions=None,
                cost_model=None, mesh=None) -> dict:
    """Per-wave measured-vs-predicted residuals for one compiled spec:
    the row schema ``BENCH_telemetry.json`` persists."""
    from ..analysis.verify import engine_of
    measured = measured_wave_times(spec, nbytes, iters, fractions, mesh)
    import jax
    cm = cost_model or CostModel.for_backend(jax.default_backend())
    predicted = cm.wave_times(spec, nbytes, 4, fractions)
    wires = wave_wire_bytes(spec, nbytes, 4, fractions)
    meas_us = [t * 1e6 for t in measured]
    pred_us = [t * 1e6 for t in predicted]
    resid_us = [m - p for m, p in zip(meas_us, pred_us)]
    return {
        "engine": engine_of(spec),
        "waves": len(wires),
        "nbytes": int(nbytes),
        "wire_bytes": [int(w) for w in wires],
        "predicted_us": [round(v, 3) for v in pred_us],
        "measured_us": [round(v, 3) for v in meas_us],
        "residual_us": [round(v, 3) for v in resid_us],
        "summary": {
            "predicted_total_us": round(sum(pred_us), 3),
            "measured_total_us": round(sum(meas_us), 3),
            "mean_abs_residual_us": round(
                sum(abs(r) for r in resid_us) / max(1, len(resid_us)), 3),
            "max_abs_residual_us": round(
                max((abs(r) for r in resid_us), default=0.0), 3),
        },
    }


def fit_calibration(wire_bytes, measured_s) -> dict:
    """Least-squares ``t = alpha + bytes / link_bw`` over measured waves
    (the CostModel's two constants).  Degenerate samples (fewer than two
    distinct wire widths, or a non-positive slope on noisy hosts) pin
    ``link_bw`` high so alpha alone carries the fit."""
    import numpy as np
    b = np.asarray(wire_bytes, dtype=float)
    t = np.asarray(measured_s, dtype=float)
    if b.size < 2 or np.ptp(b) == 0.0:
        return {"alpha": float(t.mean()) if t.size else 0.0,
                "link_bw": 1e15}
    slope, intercept = np.polyfit(b, t, 1)
    return {"alpha": max(float(intercept), 0.0),
            "link_bw": float(1.0 / slope) if slope > 0 else 1e15}


def register_measured(wire_bytes, measured_s, backend=None) -> dict:
    """Fit a calibration from measured waves and feed it back into the
    registry ``CostModel.for_backend`` consults.  Returns the registered
    row (``{"backend", "alpha", "link_bw"}``)."""
    cal = fit_calibration(wire_bytes, measured_s)
    if backend is None:
        import jax
        backend = jax.default_backend()
    CostModel.register_calibration(backend, **cal)
    return {"backend": backend, **cal}
