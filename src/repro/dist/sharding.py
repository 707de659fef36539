"""Logical-axis sharding rules: axis-name tuples -> ``PartitionSpec``.

Every ``init_*`` in :mod:`repro.models` returns a params tree plus a parallel
tree of logical axis names (("embed", "mlp"), ("vocab", "embed"), ...).
``spec_for`` turns one such tuple into a ``PartitionSpec`` for a mesh:

  * "batch" dims map to the data-parallel mesh axes ("pod", "data");
  * exactly one tensor dim maps to the "model" axis, chosen by Megatron-style
    priority (experts > vocab > mlp > heads > kv_heads > head_dim), skipping
    dims the mesh extent does not divide;
  * with ``fsdp=True`` (ZeRO-3) the largest remaining divisible named dim is
    additionally split over the data axes;
  * "layers" (the scan-stacked leading dim) and unnamed dims stay replicated;
    any axis name whose mesh axis is absent falls back to replicated.

Divisibility is always checked against the mesh axis sizes, so shapes that
do not tile (heads=28 on a 16-way model axis, batch=1 on a 16-way data axis)
degrade gracefully instead of erroring.
"""
from __future__ import annotations

import jax

PartitionSpec = jax.sharding.PartitionSpec

# data-parallel mesh axes, outermost first (flattened row-major = DP rank)
DATA_AXES = ("pod", "data")
MODEL_AXIS = "model"
# tensor-parallel candidates, highest priority first
TENSOR_AXES = ("experts", "vocab", "mlp", "heads", "kv_heads", "head_dim")
# never sharded: scan-stacked layer dim must stay whole for lax.scan
UNSHARDED_AXES = ("layers",)


def _axis_sizes(mesh) -> dict:
    """axis name -> extent; works on real meshes and duck-typed stand-ins
    (anything with ``.axis_names`` and ``.devices.shape``)."""
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


def _dp_axes(sizes: dict):
    names = tuple(a for a in DATA_AXES if a in sizes)
    total = 1
    for a in names:
        total *= sizes[a]
    return names, total


def _dp_entry(names):
    return names[0] if len(names) == 1 else names


def spec_for(axes, shape, mesh, fsdp: bool = True) -> PartitionSpec:
    """PartitionSpec for one array with logical ``axes`` and ``shape``."""
    axes = tuple(axes)
    shape = tuple(shape)
    sizes = _axis_sizes(mesh)
    dp_names, dp_total = _dp_axes(sizes)
    model_n = sizes.get(MODEL_AXIS, 0)
    entries = [None] * len(shape)

    # 1. batch dims -> data axes
    for i, (ax, dim) in enumerate(zip(axes, shape)):
        if ax == "batch" and dp_names and dim and dim % dp_total == 0:
            entries[i] = _dp_entry(dp_names)

    # 2. one tensor dim -> model axis, by priority then divisibility
    if model_n:
        best = None
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if ax in TENSOR_AXES and entries[i] is None and dim \
                    and dim % model_n == 0:
                rank = TENSOR_AXES.index(ax)
                if best is None or rank < best[0]:
                    best = (rank, i)
        if best is not None:
            entries[best[1]] = MODEL_AXIS

    # 3. FSDP: largest remaining divisible named dim -> data axes (skipped
    # when a batch dim already holds them -- an axis may appear only once)
    if fsdp and dp_names and all(e is None or e == MODEL_AXIS
                                 for e in entries):
        best = None
        for i, (ax, dim) in enumerate(zip(axes, shape)):
            if ax is None or ax == "batch" or ax in UNSHARDED_AXES:
                continue
            if entries[i] is None and dim and dim % dp_total == 0:
                if best is None or dim > best[0]:
                    best = (dim, i)
        if best is not None:
            entries[best[1]] = _dp_entry(dp_names)

    return PartitionSpec(*entries)


def owner_stripe_spec(mesh) -> PartitionSpec:
    """PartitionSpec for ZeRO-1 owner-stripe state: the leading axis of a
    ``(ndp, kmax, smax)`` array is the owner device, split over the
    data-parallel mesh axes so device ``d`` holds only its own stripe
    rows; the stripe dims stay unsplit.  Meshes without a DP extent get
    the replicated spec (zero1 has nothing to shard there)."""
    names, total = _dp_axes(_axis_sizes(mesh))
    if not names or total <= 1:
        return PartitionSpec()
    return PartitionSpec(_dp_entry(names))


def train_state_shardings(axes_tree, params_tree, mesh, fsdp: bool = True):
    """``(params, AdamW state)`` NamedSharding trees for the dense train
    step: params by :func:`tree_shardings`, the moments as their params,
    the step count replicated.  Place the state with it and jit the step
    with it as ``out_shardings``: the new state then comes back in the
    layout it went in with, and the jitted step keeps one input
    signature (no retrace, no recompile) from step to step."""
    from ..optim.adamw import OptState
    pshard = tree_shardings(axes_tree, params_tree, mesh, fsdp=fsdp)
    rep = jax.sharding.NamedSharding(mesh, PartitionSpec())
    return pshard, OptState(rep, pshard, pshard)


def zero1_state_shardings(opt_state, mesh):
    """NamedSharding tree for a :class:`repro.optim.sharded.ShardedOptState`:
    ``mu`` / ``nu`` take :func:`owner_stripe_spec`, the scalar step
    replicates.  Use as jit in_shardings / device_put placement."""
    stripe = jax.sharding.NamedSharding(mesh, owner_stripe_spec(mesh))
    rep = jax.sharding.NamedSharding(mesh, PartitionSpec())
    return type(opt_state)(rep, stripe, stripe)


def _is_axes_leaf(x) -> bool:
    """A leaf of an axes tree is a (possibly empty) tuple of names/Nones;
    tuples of sub-trees (e.g. a (k, v) cache pair) are interior nodes."""
    return isinstance(x, tuple) and \
        all(a is None or isinstance(a, str) for a in x)


def tree_shardings(axes_tree, params_tree, mesh, fsdp: bool = True):
    """NamedSharding tree matching ``params_tree`` (arrays or
    ShapeDtypeStructs), driven by the parallel ``axes_tree`` of logical axis
    tuples produced by the model inits."""
    def one(ax, p):
        spec = spec_for(ax, p.shape, mesh, fsdp=fsdp)
        return jax.sharding.NamedSharding(mesh, spec)

    return jax.tree.map(one, axes_tree, params_tree, is_leaf=_is_axes_leaf)
