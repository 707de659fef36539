"""Elastic rescaling + failure drills: resume a checkpoint onto a DIFFERENT
mesh, rebuild the EDST collective schedule for the new fabric, and exercise
the precompiled failure-class schedules end to end.

The two halves of elasticity here:
  * parameters/optimizer state: checkpoints store fully-gathered host
    arrays; ``restore`` re-places them with the *new* mesh's shardings
    (logical shapes are mesh-independent, so any mesh whose divisibility
    rules accept the shapes works);
  * collectives: the EDST packing is a function of the device fabric, so a
    changed fabric (fewer pods, a resized data axis, a failed chip excluded)
    gets a fresh maximal packing via the paper's constructions (or
    Roskind-Tarjan on an irregular residual fabric).

``failure_drill`` is the third half :-) -- the driver-side loop for
:mod:`repro.dist.fault`: inject link failures into the DP fabric, pick the
recovery schedule (a scalar id flip, no retrace), verify every chosen
program with the packet-level simulator, and report effective allreduce
bandwidth before/after each event and after the Roskind-Tarjan rebuild.

    python -m repro.launch.elastic --ckpt-dir /tmp/ck \
        --from-mesh 4,4 --to-mesh 2,8 --arch smollm-135m --reduced
    python -m repro.launch.elastic --failure-drill --to-mesh 4,4 --events 3
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import configs
from repro.ckpt import latest_step, restore
from repro.core.collectives import CostModel
from repro.core.edst_rt import max_edsts
from repro.core.fault import FailureEvent
from repro.core.graph import Graph
from repro.dist import sharding as shd
from repro.dist.chaos import out_of_class_burst
from repro.dist.fault import FaultAwareAllreduce, NoScheduleError
from repro.dist.steps import (dp_axes_of, edst_spec_for_mesh,
                              fault_runtime_for_mesh)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.api import build
from repro.optim import AdamW, cosine_schedule


def reshard_checkpoint(api, opt, ckpt_dir: str, mesh):
    """Load the latest checkpoint and place it on ``mesh``.  Returns
    (params, opt_state, step)."""
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params, axes = api.init(key)
        opt_state = opt.init(params)
        pshard = shd.tree_shardings(axes, params, mesh)
        oshard = type(opt_state)(
            jax.tree.map(lambda _: jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()), opt_state.step),
            pshard, pshard)
        state, step, _ = restore(ckpt_dir, {"p": params, "o": opt_state},
                                 shardings={"p": pshard, "o": oshard})
    if state is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    return state["p"], state["o"], step


def rebuild_schedule(mesh, dp_torus_shape=None, engine: str = "pipelined",
                     schedule: str = "greedy"):
    """EDST allreduce spec for the (possibly new) DP fabric, or None when
    the mesh has no DP extent (single data shard: nothing to sync).
    Rescales that land on an already-compiled fabric hit the spec caches
    (``edst_spec_for_mesh`` memoizes per (topology, axes, engine,
    schedule), the spec compilers per schedule key) and return the
    IDENTICAL spec object -- a jitted executor taking the spec statically
    never retraces.  ``schedule="composed"`` routes through the
    compositional product-schedule compiler, whose caches key on
    ``StarProduct.cache_key()``."""
    from repro.dist.steps import dp_size
    if dp_size(mesh) <= 1:
        return None
    return edst_spec_for_mesh(tuple(mesh.devices.shape),
                              tuple(mesh.axis_names), dp_torus_shape,
                              engine=engine, schedule=schedule)


# Surviving-fabric runtimes, keyed by (n, surviving edge set, axes,
# engine): a drill (or a flapping node) that lands on an already-seen
# residual fabric reuses the runtime's entries -- every entry spec is the
# identical object, so nothing downstream retraces -- instead of
# re-running Roskind-Tarjan and 2k+1 spec compiles per event.
_RESCALE_CACHE: dict = {}


def rescale_after_node_loss(runtime, event: FailureEvent,
                            ) -> tuple:
    """Elastic node-loss recovery: drop the dead nodes entirely, relabel
    the surviving chips 0..n'-1, repack a maximal EDST set on the
    residual fabric (Roskind-Tarjan), and build a fresh
    :class:`repro.dist.fault.FaultAwareAllreduce` for it.  Returns
    ``(new_runtime, relabel)`` where ``relabel[old_vertex] == new_vertex``
    for every survivor -- the map drivers use to re-place per-rank state
    (the same relabeling ``repro.core.fault`` applies internally).
    Raises :class:`NoScheduleError` when the survivors are disconnected.

    Repeat rescales onto the same surviving fabric are served from
    ``_RESCALE_CACHE``: the returned runtime shares the cached entries
    (and jitted reshard gathers) object-for-object, with only the
    history fresh.
    """
    dead = event.dead_links(runtime.graph)
    residual = runtime.graph.without_edges(dead)
    alive = [v for v in range(runtime.graph.n) if v not in event.nodes]
    relabel = {v: i for i, v in enumerate(alive)}
    sub = Graph(len(alive),
                {(relabel[u], relabel[v]) for u, v in residual.edges
                 if u in relabel and v in relabel}, name="rescaled")
    if not sub.is_connected():
        raise NoScheduleError(
            f"surviving fabric ({len(alive)} nodes) disconnected; "
            "cannot rescale")
    key = (sub.n, frozenset(sub.edges), runtime.axes, runtime.engine)
    base = _RESCALE_CACHE.get(key)
    if base is None:
        trees, _ = max_edsts(sub)
        if not trees:
            raise NoScheduleError("surviving fabric packs no spanning tree")
        base = FaultAwareAllreduce.build(sub, trees, runtime.axes,
                                         engine=runtime.engine)
        _RESCALE_CACHE[key] = base
    new_rt = FaultAwareAllreduce(base.graph, base.axes, base.entries,
                                 engine=base.engine,
                                 _reshard_cache=base._reshard_cache)
    new_rt.history = runtime.history + [("rescaled", len(alive))]
    return new_rt, relabel


def failure_drill(runtime, n_events: int = 3, nbytes: float = 64 << 20,
                  seed: int = 0, cost_model: CostModel | None = None,
                  kinds=("link",)) -> dict:
    """Inject ``n_events`` seeded failures into the fabric (cycling
    through ``kinds``), observe the runtime's recovery choice after each,
    and report effective bandwidth: healthy -> recovered per event.

      * ``"link"``  -- a single-link kill: recovery is a precompiled
        schedule-id flip (``on_failure``), falling back to a dynamic
        repack only if no class survives;
      * ``"burst"`` -- an out-of-class multi-link burst (grown with
        :func:`repro.dist.chaos.out_of_class_burst` until no precompiled
        class survives), forcing the ``with_rebuild`` Roskind-Tarjan
        path;
      * ``"node"``  -- a node loss: checkpointless here, exercising
        :func:`rescale_after_node_loss` (relabel survivors + repack).
        The rescaled fabric has fewer chips, so its ``bw_retained`` is
        relative to a *different* healthy baseline and may exceed 1.

    Events are independent -- each is injected into the healthy runtime.
    Each chosen schedule is validated with the packet-level simulator
    (``repro.core.collectives.simulate_allreduce``), so the drill runs on
    any host -- no devices needed; the shard_map execution path of the same
    programs is covered by tests/test_fault_runtime_jax.py and the chaos
    soak (benchmarks/chaos_soak.py).
    """
    cm = cost_model or CostModel()
    rng = np.random.RandomState(seed)
    healthy_bw = runtime.effective_bandwidth(nbytes, 0, cm)
    report = {"n": runtime.graph.n, "k": runtime.k, "nbytes": nbytes,
              "healthy_gbps": round(healthy_bw / 1e9, 3), "events": []}
    tree_links = sorted(set().union(
        *(ts.tree for ts in runtime.entries[0].sched.trees)))
    for i in range(n_events):
        kind = kinds[i % len(kinds)]
        if kind == "link":
            link = tree_links[rng.randint(len(tree_links))]
            event = FailureEvent(links=frozenset({link}))
            rec = {"event": i, "kind": "link", "dead_link": list(link)}
            try:
                rt = runtime.on_failure(event)      # precompiled: id flip only
                deg = runtime.on_failure(event, prefer="degraded")
                rec.update({
                    "schedule": rt.entry.name, "schedule_id": rt.active,
                    "k": rt.entry.k,
                    "depth": rt.entry.depth,
                    "sim_ok": rt.verify_entry(rt.active),
                    "gbps": round(rt.effective_bandwidth(nbytes, rt.active,
                                                         cm) / 1e9, 3),
                    "degraded_gbps": round(
                        deg.effective_bandwidth(nbytes, deg.active, cm)
                        / 1e9, 3),
                })
            except NoScheduleError:                 # dynamic repack
                rt = runtime.with_rebuild(event)
                rec.update({
                    "schedule": "with_rebuild", "schedule_id": 0, "k": rt.k,
                    "depth": rt.entry.depth,
                    "sim_ok": rt.verify_entry(0),
                    "gbps": round(rt.effective_bandwidth(nbytes, 0, cm)
                                  / 1e9, 3),
                })
        elif kind == "burst":
            burst = out_of_class_burst(runtime,
                                       np.random.default_rng(seed + i))
            event = FailureEvent(links=frozenset(burst))
            assert not runtime.valid_ids(event)
            rt = runtime.with_rebuild(event)
            rec = {"event": i, "kind": "burst",
                   "dead_links": sorted(list(e) for e in burst),
                   "schedule": "with_rebuild", "schedule_id": 0, "k": rt.k,
                   "depth": rt.entry.depth,
                   "sim_ok": rt.verify_entry(0),
                   "gbps": round(rt.effective_bandwidth(nbytes, 0, cm)
                                 / 1e9, 3)}
        elif kind == "node":
            v = int(rng.randint(runtime.graph.n))
            event = FailureEvent(nodes=frozenset({v}))
            rt, relabel = rescale_after_node_loss(runtime, event)
            rec = {"event": i, "kind": "node", "dead_node": v,
                   "schedule": "rescale", "schedule_id": 0,
                   "n_after": rt.graph.n, "k": rt.k,
                   "depth": rt.entry.depth,
                   "sim_ok": rt.verify_entry(0),
                   "gbps": round(rt.effective_bandwidth(nbytes, 0, cm)
                                 / 1e9, 3)}
        else:
            raise ValueError(f"unknown drill kind {kind!r} "
                             "(not in ('link', 'burst', 'node'))")
        rec["bw_retained"] = round(rec["gbps"] * 1e9 / healthy_bw, 3)
        report["events"].append(rec)
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--to-mesh", required=True)
    ap.add_argument("--failure-drill", action="store_true",
                    help="no checkpoint: build the elastic EDST runtime for "
                         "the DP fabric of --to-mesh, inject failures, "
                         "report recovery + bandwidth as JSON")
    ap.add_argument("--events", type=int, default=3)
    ap.add_argument("--nbytes", type=int, default=64 << 20)
    ap.add_argument("--drill-kinds", default="link,burst,node",
                    help="comma list of failure kinds the drill cycles "
                         "through: link (schedule flip), burst "
                         "(out-of-class with_rebuild), node (elastic "
                         "rescale)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.failure_drill:
        dims = tuple(int(x) for x in args.to_mesh.split(","))
        runtime = fault_runtime_for_mesh((int(np.prod(dims)), 1),
                                         ("data", "model"),
                                         dp_torus_shape=dims)
        report = failure_drill(runtime, n_events=args.events,
                               nbytes=args.nbytes,
                               kinds=tuple(args.drill_kinds.split(",")))
        print(json.dumps(report, indent=2))
        return report

    if args.ckpt_dir is None:
        ap.error("--ckpt-dir is required unless --failure-drill")
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build(cfg)
    dims = tuple(int(x) for x in args.to_mesh.split(","))
    names = ("pod", "data", "model")[-len(dims):]
    mesh = make_mesh(dims, names)
    opt = AdamW(cosine_schedule(3e-4, 10, 100))
    params, opt_state, step = reshard_checkpoint(api, opt, args.ckpt_dir, mesh)
    spec = rebuild_schedule(mesh)
    k = spec.k if spec is not None else 0
    print(f"[elastic] resumed step {step} onto mesh {dims}; "
          f"EDST schedule rebuilt with k={k} trees")
    return params, opt_state, step


if __name__ == "__main__":
    main()
