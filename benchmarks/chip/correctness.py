"""The comparison that decides ``correct``.

Three numbers, each against a limit of the cell's own
(``limits/<workload>.json``):

* ``loss_gap``: the largest gap between the program's loss and the
  reference's over every step the reference follows;
* ``grad_gap``: the worst leaf's gap between the norms of step 1's
  gradient as the optimizer gets it (before clipping), worked out from
  the program's Adam state after one step;
* ``update_gap``: the worst leaf's gap between the norms of the
  parameters' change over the first steps.

A cell's limits file names the numbers it compares.

A leaf is one published parameter tensor: one layer's slice of a
stacked array, one (layer, expert) slice of a stacked array that the
reference names as an expert leaf, or an unstacked array.  A leaf's gap is
``|program - reference|`` over the larger of the reference's norm of
that leaf and of the median leaf.  Leaves whose reference gradient is
under a thousandth of the median leaf's move by rounding alone and are
left out of ``update_gap``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
TINY_GRAD = 1e-3


def slice_norms(tree, experts=()) -> dict:
    """Per-leaf L2 norms (jittable): ``{path: norm}`` for unstacked
    arrays, ``{path: (layers,) norms}`` for the stacked ones under
    ``layers``, and ``{path: (layers, experts) norms}`` for the stacked
    ones named in ``experts``, whose second axis is the expert."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = ".".join(str(getattr(k, "key", k)) for k in path)
        x = leaf.astype(jnp.float32)
        if name in experts:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(2, x.ndim))))
        elif name.startswith("layers."):
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def flatten(norms: dict) -> dict:
    """``{leaf: float}``, with a stacked array's layers as ``name[i]``
    and an expert leaf's (layer, expert) slices as ``name[i,e]``."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        for idx in np.ndindex(v.shape):
            out[f"{name}[{','.join(map(str, idx))}]" if idx else name] = \
                float(v[idx])
    return out


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """``{leaf: gap}`` over the leaves in ``keep`` (default all)."""
    if set(prog) != set(ref):
        raise ValueError("program and reference name different leaves: "
                         f"{sorted(set(prog) ^ set(ref))[:5]}")
    med = float(np.median(list(ref.values())))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in sorted(ref)
            if keep is None or k in keep}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers for one run.  ``prog`` and ``ref`` hold ``losses``
    (one per step), ``grad`` and ``update`` (``{leaf: norm}``)."""
    if len(prog["losses"]) != len(ref["losses"]):
        raise ValueError("program and reference ran different step counts")
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    med = float(np.median(list(ref["grad"].values())))
    moving = {k for k, v in ref["grad"].items() if v >= TINY_GRAD * med}
    grad = leaf_gaps(prog["grad"], ref["grad"])
    upd = leaf_gaps(prog["update"], ref["update"], keep=moving)
    return {"loss_gap": max(gaps), "grad_gap": max(grad.values()),
            "update_gap": max(upd.values()),
            "step_loss_gaps": gaps,
            "worst_grad_leaf": max(grad, key=grad.get),
            "worst_update_leaf": max(upd, key=upd.get),
            "left_out_of_update": sorted(set(ref["grad"]) - moving)}


def judge(numbers: dict, limits: dict):
    """``(correct, {name: {"value", "limit"}})``: correct when every
    number the limits name is finite and at or under its limit.  A cell
    leaves a number out of its limits where no control or fault reading
    separates from the sound runs' (PERF.md says which, with readings)."""
    checks, ok = {}, True
    for name in NUMBERS:
        if name not in limits:
            continue
        v, lim = float(numbers[name]), float(limits[name])
        checks[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, checks
