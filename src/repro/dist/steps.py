"""Sharded train steps with selectable gradient synchronization.

``make_train_step`` builds ``step(params, opt_state, batch) -> (params,
opt_state, metrics)`` for a mesh, with ``mode`` choosing how data-parallel
gradients are combined:

  * ``"gspmd"``   -- no manual collectives: the loss is computed on the
    global batch and XLA's SPMD partitioner inserts whatever all-reduces the
    (optional FSDP) shardings imply;
  * ``"psum_dp"`` -- explicit ``shard_map`` over the data axes with a
    ``jax.lax.psum`` gradient all-reduce (the TPU-native baseline);
  * ``"edst"``    -- the same ``shard_map``, but gradients travel the k-tree
    allreduce built from the paper's edge-disjoint spanning trees on the DP
    fabric (:func:`edst_spec_for_mesh`), chunks striped across trees.

All three modes compute identical gradients (up to float reassociation), so
they can be A/B'd freely; ``grad_accum`` microbatches the local batch and
``quantize`` sends int8 chunks over the trees.  Passing ``fault_runtime``
(see :mod:`repro.dist.fault`) makes the ``edst`` mode failure-event aware:
the step takes a traced ``schedule_id`` selecting among precompiled
healthy/degraded/rebuilt tree programs, so link failures are handled by a
scalar flip instead of a retrace.

``zero1=True`` (``mode="edst"``, striped engine) replaces the gradient
allreduce + dense optimizer with the ZeRO-1 pipeline: reduce-scatter the
gradients onto owner stripes, run the sharded AdamW of
:mod:`repro.optim.sharded` in the scattered domain, and allgather only
the updated params -- fewer collective waves per step than the composed
``striped_allreduce`` and ~n-fold less optimizer memory.

``edst_spec_for_mesh`` maps a device mesh to the star-product decomposition
of its data-parallel fabric.  By default the DP axes themselves are taken as
the torus dimensions; ``dp_torus_shape`` overrides that for pods whose
logical mesh flattens a different physical topology (e.g. a pure-DP (16, 1)
mesh that is physically a 4x4 torus -- the override recovers the 2-EDST
maximal packing where the flat view would see only a 16-ring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from ..core import topologies as topo
from ..optim.sharded import ShardedAdamW, ShardedOptState, decay_mask
from ..core.collectives import (FusedAllreduceSpec, PipelinedAllreduceSpec,
                                StripedCollectiveSpec, allreduce_schedule,
                                fused_spec_from_schedule,
                                pipelined_spec_from_schedule,
                                striped_spec_from_schedule, wave_wire_bytes)
from ..core.edst_star import star_edsts
from . import sharding as shd
from .fault import FaultAwareAllreduce
from .striped import stripe_slices, tree_allgather, tree_reduce_scatter
from .tree_allreduce import tree_allreduce

SYNC_MODES = ("gspmd", "psum_dp", "edst")


# ---------------------------------------------------------------------------
# mesh introspection
# ---------------------------------------------------------------------------

def dp_axes_of(mesh):
    """The data-parallel mesh axes present, outermost first."""
    return tuple(a for a in tuple(mesh.axis_names) if a in shd.DATA_AXES)


def dp_size(mesh) -> int:
    sizes = shd._axis_sizes(mesh)
    n = 1
    for a in dp_axes_of(mesh):
        n *= sizes[a]
    return n


def dp_fabric_for_mesh(mesh_shape, axis_names, dp_torus_shape=None):
    """The data-parallel fabric of a device mesh: (star_product, dp_axis_names).

    The DP fabric is the sub-mesh spanned by the ("pod", "data") axes; its
    physical ICI graph is taken to be the torus over those extents (row-major
    vertex ids = flattened DP rank, matching ``device_topology``).
    ``dp_torus_shape`` overrides the physical shape when the logical mesh
    flattens it (product must equal the DP extent).
    """
    axis_names = tuple(axis_names)
    dims = [int(s) for a, s in zip(axis_names, mesh_shape)
            if a in shd.DATA_AXES]
    names = tuple(a for a in axis_names if a in shd.DATA_AXES)
    n = int(np.prod(dims)) if dims else 1
    if n <= 1:
        raise ValueError("mesh has no data-parallel extent to sync over")
    phys = tuple(int(d) for d in dp_torus_shape) if dp_torus_shape \
        else tuple(d for d in dims if d > 1)
    if int(np.prod(phys)) != n:
        raise ValueError(f"dp_torus_shape {phys} != DP extent {n}")
    return topo.device_topology(phys), names


@functools.lru_cache(maxsize=None)
def _edst_spec_cached(mesh_shape, axis_names, dp_torus_shape, engine,
                      schedule):
    sp, names = dp_fabric_for_mesh(mesh_shape, axis_names, dp_torus_shape)
    if schedule == "composed":
        # the compositional path never materializes the flat message DAG:
        # factor EDSTs -> star trees -> ASAP wave placement, memoized on
        # StarProduct.cache_key()
        from ..core.product_schedule import composed_spec_for_star
        return composed_spec_for_star(sp, names, engine=engine)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    if engine == "fused":
        return fused_spec_from_schedule(sched, names, schedule=schedule)
    if engine == "striped":
        return striped_spec_from_schedule(sched, names, schedule=schedule)
    return pipelined_spec_from_schedule(sched, names, schedule=schedule)


ENGINES = ("pipelined", "fused", "striped")


def edst_spec_for_mesh(
        mesh_shape, axis_names, dp_torus_shape=None,
        engine: str = "pipelined", schedule: str = "greedy"
) -> PipelinedAllreduceSpec | FusedAllreduceSpec | StripedCollectiveSpec:
    """EDST allreduce spec for the data-parallel fabric of a device mesh
    (see :func:`dp_fabric_for_mesh` for the fabric choice).  ``engine``
    picks the compiled form: ``"pipelined"`` (default -- the list-
    scheduled segment-streaming wave program), ``"striped"`` (the
    reduce-scatter/allgather program of :mod:`repro.dist.striped`:
    stripe-sized wires for bandwidth-dominated fabrics) or ``"fused"``
    (the round-aligned A/B baseline).  ``schedule`` picks the
    wave-assembly strategy (``repro.core.collectives.SCHEDULES``):
    ``"greedy"`` list scheduling, ``"search"`` the seeded hillclimb, or
    ``"composed"`` the compositional product-schedule compiler (near-
    linear compile on 10k+-node fabrics).  Specs are cached by
    (topology, axes, engine, schedule): repeated calls -- every
    train-step build, every elastic rescale probe -- return the same
    object, so jitted executors taking the spec statically never
    retrace."""
    if engine not in ENGINES:
        raise ValueError(f"engine {engine!r} not in {ENGINES}")
    return _edst_spec_cached(
        tuple(mesh_shape), tuple(axis_names),
        None if dp_torus_shape is None else tuple(dp_torus_shape), engine,
        schedule)


def fault_runtime_for_mesh(mesh_shape, axis_names, dp_torus_shape=None,
                           engine: str = "pipelined",
                           schedule: str = "greedy") -> FaultAwareAllreduce:
    """Elastic EDST runtime (precompiled degraded/rebuilt failure-class
    schedules) for the data-parallel fabric of a device mesh.  Pass the
    result to ``make_train_step(mode="edst", fault_runtime=...)`` and feed
    its schedule ids into the step's ``schedule_id`` argument.
    ``engine`` selects the compiled program form of every failure class
    (striped classes re-stripe ownership over the surviving trees);
    ``schedule`` the wave-assembly strategy of the healthy entry (failure
    classes always compile greedy: their fabrics are degraded one-offs)."""
    sp, names = dp_fabric_for_mesh(mesh_shape, axis_names, dp_torus_shape)
    return FaultAwareAllreduce.build(sp.product(), star_edsts(sp).trees,
                                     names, engine=engine,
                                     schedule=schedule)


# ---------------------------------------------------------------------------
# train step factory
# ---------------------------------------------------------------------------

_WIRE_TABLE_CACHE: dict = {}


def _entry_wire_table(entries, nbytes: int, itemsize: int):
    """Per-entry total wire bytes of a fault runtime's precompiled
    schedules as an (E,) f32 table, memoized on (spec keys, payload) so
    traced closures index it without rebuilding per trace."""
    key = (tuple((e.spec.key, e.fractions) for e in entries),
           int(nbytes), int(itemsize))
    hit = _WIRE_TABLE_CACHE.get(key)
    if hit is None:
        hit = np.asarray(
            [float(sum(wave_wire_bytes(e.spec, nbytes, itemsize,
                                       e.fractions or None)))
             for e in entries], np.float32)
        _WIRE_TABLE_CACHE[key] = hit
    return hit


def make_train_step(api, opt, mesh, mode: str = "gspmd", fsdp: bool = True,
                    grad_accum: int = 1, quantize: bool = False,
                    dp_torus_shape=None, fault_runtime=None,
                    segments="auto", engine: str = "pipelined",
                    zero1: bool = False, codec=None,
                    telemetry: bool = False):
    """Build the jittable train step.  See module docstring for ``mode``.

    ``telemetry=True`` adds a structured in-graph metrics dict (all
    scalars, no extra collectives beyond the checksum):

      * ``"sync_dev"`` -- the integrity check on the synchronized
        gradients that feeds :class:`repro.dist.health.HealthMonitor`:
        for the replicating paths (``psum_dp`` / dense ``edst``) the
        cross-replica :func:`repro.dist.health.replication_divergence`
        of a payload checksum (~0 when every replica holds identical
        sums), for the ZeRO-1 path the scattered-domain
        :func:`repro.dist.striped.rs_conservation_gap`;
      * ``"sync_grad_norm"`` -- global L2 norm of the synchronized
        gradients (the ZeRO-1 path already emits ``"grad_norm"``);
      * ``"sync_schedule_id"`` -- the traced schedule id the sync ran on
        (0 without a fault runtime);
      * ``"sync_wire_bytes"`` -- static per-step wire bytes of the EDST
        sync program (``repro.core.collectives.wave_wire_bytes`` summed;
        with a fault runtime, a precompiled per-entry table indexed by
        the traced id -- so flips move the gauge without a retrace;
        0 for ``psum_dp``/``gspmd``, whose wire XLA owns).

    Every key is present in every mode (zero-valued where it does not
    apply), so downstream consumers never branch on dict shape.

    ``engine`` (``mode="edst"``, ignored when a ``fault_runtime`` carries
    its own engine) selects the compiled allreduce form -- see
    :func:`edst_spec_for_mesh`.

    ``zero1=True`` (``mode="edst"``, striped engine only) switches to the
    ZeRO-1 step: gradients are ``tree_reduce_scatter``'d onto owner
    stripes, :class:`repro.optim.sharded.ShardedAdamW` updates params in
    the scattered domain (global-norm clip via a stripe-local partial
    norm + one scalar psum), and only the updated params are
    ``tree_allgather``'d back -- strictly fewer collective waves per
    step than the composed ``striped_allreduce`` and ~n-fold less
    optimizer memory.  The step's ``opt_state`` is then a
    :class:`repro.optim.sharded.ShardedOptState` (build it with
    ``ShardedAdamW(opt).init_for(params, spec_or_runtime, ndp)``); with a
    ``fault_runtime`` a schedule-id flip re-stripes the collectives in
    the step while ``fault_runtime.reshard_owned`` moves ``mu``/``nu``
    to the new owners outside it, both retrace-free.  ``codec`` overrides
    the gradient-wire codec policy (params always allgather full
    precision).

    ``fault_runtime`` (a :class:`repro.dist.fault.FaultAwareAllreduce`,
    ``mode="edst"`` only) makes the step failure-event aware: its signature
    becomes ``step(params, opt_state, batch, schedule_id)`` where
    ``schedule_id`` is a traced ``jnp.int32`` scalar selecting among the
    runtime's precompiled healthy/degraded/rebuilt programs -- the driver
    maps a failure-event stream to ids via ``fault_runtime.on_failure`` and
    flips the scalar, never triggering a retrace.

    ``segments`` (``mode="edst"``) streams gradient chunks down the trees
    in that many pipeline segments (``"auto"``: backend-calibrated cost
    model; see :func:`repro.dist.tree_allreduce.pipelined_tree_allreduce`).
    """
    if mode not in SYNC_MODES:
        raise ValueError(f"mode {mode!r} not in {SYNC_MODES}")
    if fault_runtime is not None and mode != "edst":
        raise ValueError("fault_runtime requires mode='edst'")
    dp = dp_axes_of(mesh)
    ndp = dp_size(mesh)
    dp_arg = dp[0] if len(dp) == 1 else tuple(dp)
    manual_dp = mode in ("psum_dp", "edst") and ndp > 1

    if zero1:
        if mode != "edst":
            raise ValueError("zero1=True requires mode='edst'")
        if not manual_dp:
            raise ValueError("zero1=True needs a data-parallel extent > 1 "
                             "to shard optimizer state over")
        if fault_runtime is None and engine != "striped":
            raise ValueError("zero1=True requires engine='striped' (the "
                             "reduce-scatter/allgather split)")

    tree_spec = fault_sync = z_rs = z_sl = z_ag = None
    if mode == "edst" and manual_dp:
        if fault_runtime is not None:
            if fault_runtime.graph.n != ndp:
                raise ValueError(
                    f"fault_runtime fabric n={fault_runtime.graph.n} != "
                    f"DP extent {ndp}; rebuild it with fault_runtime_for_mesh")
            if zero1:
                z_rs, z_sl, z_ag = fault_runtime.make_zero1_sync(quantize,
                                                                 codec)
            else:
                fault_sync = fault_runtime.make_allreduce(quantize,
                                                          segments=segments)
        else:
            tree_spec = edst_spec_for_mesh(tuple(mesh.devices.shape),
                                           tuple(mesh.axis_names),
                                           dp_torus_shape, engine=engine)
            if zero1:
                # same three primitives as the fault runtime's switched
                # forms, on the single healthy spec (sid ignored); params
                # allgather full precision (see make_zero1_sync)
                def z_rs(flat, sid):
                    return tree_reduce_scatter(flat, tree_spec,
                                               quantize=quantize, codec=codec)

                def z_sl(flat, sid):
                    return stripe_slices(flat, tree_spec)

                def z_ag(owned, sid, shape):
                    return tree_allgather(owned, tree_spec, shape)

    # FSDP is expressed through the shardings callers place params/opt state
    # with and jit the step with (``sharding.train_state_shardings`` as
    # in/out shardings) -- the step body itself adds no sharding
    # constraints.  JAX 0.4.x miscompiled in-step constraints in the
    # remat'd scan backward (wrong gradients alongside "Involuntary full
    # rematerialization" warnings); that has not been re-tested on 0.9.
    del fsdp

    def _tree_grad_norm(grads):
        return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(grads)))

    def _wire_gauge(nbytes, itemsize, sid):
        """Static wire bytes of the sync program this step runs.  With a
        fault runtime the per-entry totals are a compile-time table the
        traced schedule id indexes, so schedule flips move the gauge
        without retracing."""
        if fault_runtime is not None:
            vals = _entry_wire_table(fault_runtime.entries, nbytes, itemsize)
            table = jnp.asarray(vals, jnp.float32)
            return table[jnp.clip(sid, 0, len(fault_runtime.entries) - 1)]
        if tree_spec is not None:
            return jnp.float32(sum(wave_wire_bytes(tree_spec, nbytes,
                                                   itemsize)))
        return jnp.float32(0.0)

    def loss_of(p, b):
        # differentiated: the backward pass shows as
        # transpose(jvp(step/model)), recomputation under it
        with jax.named_scope("step/model"):
            return api.loss_fn(p, b)

    vg = jax.value_and_grad(loss_of, has_aux=True)

    def local_loss_and_grads(params, batch):
        """Loss + grads on the (device-local) batch, microbatched when
        grad_accum > 1 (mean of microbatch grads == full-batch grad)."""
        if grad_accum == 1:
            (loss, aux), grads = vg(params, batch)
            return loss, aux, grads
        micro = jax.tree.map(
            lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                + x.shape[1:]), batch)

        def body(carry, mb):
            loss_sum, grads_sum = carry
            (loss, aux), grads = vg(params, mb)
            return (loss_sum + loss,
                    jax.tree.map(jnp.add, grads_sum, grads)), aux

        zeros = jax.tree.map(lambda p_: jnp.zeros(p_.shape, p_.dtype), params)
        (loss_sum, grads_sum), auxs = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        loss = loss_sum / grad_accum
        grads = jax.tree.map(lambda g: g / grad_accum, grads_sum)
        aux = jax.tree.map(jnp.mean, auxs)
        return loss, aux, grads

    def synced_loss_and_grads(params, batch, schedule_id=None):
        if not manual_dp:
            loss, aux, grads = local_loss_and_grads(params, batch)
            if telemetry:  # nothing synchronized; divergence vacuously 0
                zero = jnp.zeros((), jnp.float32)
                return loss, aux, grads, {
                    "sync_dev": zero,
                    "sync_grad_norm": _tree_grad_norm(grads),
                    "sync_schedule_id": jnp.int32(0),
                    "sync_wire_bytes": zero}
            return loss, aux, grads

        def local(p, b, sid):
            loss, aux, grads = local_loss_and_grads(p, b)
            with jax.named_scope("step/sync"):
                loss = jax.lax.pmean(loss, dp_arg)
                aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp_arg), aux)
                if mode == "psum_dp":
                    grads = jax.tree.map(
                        lambda g: jax.lax.psum(g, dp_arg) / ndp, grads)
                    flat = ravel_pytree(grads)[0] if telemetry else None
                else:
                    flat, unravel = ravel_pytree(grads)
                    if fault_sync is not None:
                        flat = fault_sync(flat, sid)
                    else:
                        flat = tree_allreduce(flat, tree_spec,
                                              quantize=quantize,
                                              segments=segments)
                    # divide leaf by leaf: the summed vector then feeds
                    # the unravel's slices as the engine returns it, and
                    # XLA keeps fewer gradient-sized copies alive at once
                    # (the same values wherever a leaf has the vector's
                    # dtype, or the divisor is a power of two)
                    grads = jax.tree.map(lambda g: g / ndp, unravel(flat))
                if telemetry:
                    from .health import (payload_checksum,
                                         replication_divergence)
                    dev = replication_divergence(payload_checksum(flat),
                                                 dp_arg)
                    itemsize = jnp.dtype(flat.dtype).itemsize
                    wire = (_wire_gauge(flat.size * itemsize, itemsize, sid)
                            if mode == "edst" else jnp.float32(0.0))
                    return loss, aux, grads, {
                        "sync_dev": dev,
                        "sync_grad_norm": _tree_grad_norm(grads),
                        "sync_schedule_id": jnp.asarray(sid, jnp.int32),
                        "sync_wire_bytes": wire}
            return loss, aux, grads

        # Fully-manual shard_map: params replicate and the model axis is
        # unused inside, so TP/FSDP do not compose with the manual sync
        # modes here.  Keeping only the DP axes Manual (axis_names=set(dp))
        # is the right composition, but JAX 0.4.x's XLA hard-crashed on it
        # ("Check failed: sharding.IsManualSubgroup()") in the remat'd
        # scan; not re-tested on 0.9.  Production TP+FSDP meshes use
        # mode="gspmd" meanwhile.
        if schedule_id is None:
            schedule_id = jnp.int32(0)
        outs = (P(), P(), P()) + ((P(),) if telemetry else ())
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(P(), P(dp_arg), P()),
                             out_specs=outs,
                             check_vma=False)(params, batch, schedule_id)

    if zero1:
        sopt = ShardedAdamW(opt)

        def zero1_local(p, b, sid, step_count, mu, nu):
            """The whole ZeRO-1 step body, inside shard_map: grads ->
            reduce-scatter -> sharded AdamW on owner stripes ->
            allgather of updated params only.  mu/nu arrive as this
            device's (1, kmax, smax) block of the global state."""
            loss, aux, grads = local_loss_and_grads(p, b)
            with jax.named_scope("step/sync"):
                loss = jax.lax.pmean(loss, dp_arg)
                aux = jax.tree.map(lambda a: jax.lax.pmean(a, dp_arg), aux)
                flat_g, _ = ravel_pytree(grads)
                owned_g = z_rs(flat_g, sid) / ndp
            with jax.named_scope("step/optimizer"):
                flat_p, unravel = ravel_pytree(p)
                f32 = flat_p.astype(jnp.float32)
                owned_p = z_sl(f32, sid)
                owned_d = z_sl(decay_mask(p, opt.weight_decay), sid)
                new_count = step_count + 1
                gnorm = jnp.sqrt(jax.lax.psum(sopt.partial_sumsq(owned_g),
                                              dp_arg))
                new_op, new_mu, new_nu, lr = sopt.update_stripes(
                    owned_p, owned_g, owned_d, mu[0], nu[0], new_count,
                    gnorm)
            with jax.named_scope("step/sync"):
                new_flat = z_ag(new_op, sid, f32.shape)
                new_params = unravel(new_flat.astype(flat_p.dtype))
            om = {"grad_norm": gnorm, "lr": lr}
            if telemetry:
                from .striped import rs_conservation_gap
                om["sync_dev"] = rs_conservation_gap(flat_g / ndp, owned_g,
                                                     dp_arg)
                itemsize = jnp.dtype(flat_g.dtype).itemsize
                om["sync_grad_norm"] = gnorm
                om["sync_schedule_id"] = jnp.asarray(sid, jnp.int32)
                om["sync_wire_bytes"] = _wire_gauge(
                    flat_g.size * itemsize, itemsize, sid)
            return loss, aux, new_params, new_mu[None], new_nu[None], om

        def _zstep(params, opt_state, batch, schedule_id=None):
            if schedule_id is None:
                schedule_id = jnp.int32(0)
            loss, aux, new_params, new_mu, new_nu, om = jax.shard_map(
                zero1_local, mesh=mesh,
                in_specs=(P(), P(dp_arg), P(), P(), P(dp_arg), P(dp_arg)),
                out_specs=(P(), P(), P(), P(dp_arg), P(dp_arg), P()),
                check_vma=False)(params, batch, schedule_id,
                                 opt_state.step, opt_state.mu, opt_state.nu)
            new_state = ShardedOptState(opt_state.step + 1, new_mu, new_nu)
            metrics = {"loss": loss, **om, **aux}
            return new_params, new_state, metrics

        if fault_runtime is None:
            def zstep(params, opt_state, batch):
                return _zstep(params, opt_state, batch)
            return zstep

        def zfault_step(params, opt_state, batch, schedule_id):
            return _zstep(params, opt_state, batch, schedule_id)
        return zfault_step

    def _step(params, opt_state, batch, schedule_id=None):
        out = synced_loss_and_grads(params, batch, schedule_id)
        loss, aux, grads = out[:3]
        with jax.named_scope("step/optimizer"):
            new_params, new_state, om = opt.apply(params, grads, opt_state)
        metrics = {"loss": loss, **om, **aux}
        if telemetry:
            metrics.update(out[3])
        return new_params, new_state, metrics

    if fault_runtime is None:
        def step(params, opt_state, batch):
            return _step(params, opt_state, batch)
        return step

    # fault-aware contract: always 4 args, even when the mesh has no DP
    # extent (schedule_id is then accepted and ignored -- nothing to sync)
    def fault_step(params, opt_state, batch, schedule_id):
        return _step(params, opt_state, batch, schedule_id)
    return fault_step
