"""Allreduce executor + schedule-compiler benchmark (the repo's perf
trajectory for the hot collective).

Three families of entries:

  * ``exec/<fabric>/<engine>`` -- wall-clock of one allreduce on 16 fake
    host devices: the pipelined segmented engine (the default; plus its
    S in {1,2,4,8} segment sweep and the ``segments="auto"`` pick, which
    the row records), the striped reduce-scatter/allgather engine
    (stripe-sized wires, ~2x the wave count: slower on this
    alpha-dominated host -- that IS the datapoint the engine-selection
    matrix documents), the ``zero1`` train-step stand-in (reduce-scatter
    -> owner-stripe update -> params allgather: the same stripe program
    minus the gradient allgather, so its row records ``waves`` vs
    ``composed_waves``), the fused global-round and per-tree baselines,
    and ``jax.lax.psum``, each with and without the int8 wire, on the
    (4,4) and (2,8) torus DP fabrics.  Cases are timed *interleaved*
    (every engine once per block, best block wins) so slow drift on
    shared CI hosts cannot skew one engine's row;
  * ``compile/<fabric>/<center>`` -- schedule-compile time of the
    depth-minimizing root search: the CSR double-BFS center
    (``repro.core.csr``) against the historical O(n^2) every-vertex
    probe, on the paper's diameter-2/3 fabrics (Slim Fly, PolarStar) and
    a 1024-node torus;
  * ``calibration/<backend>`` -- measured CostModel constants (per-
    collective alpha from the pipelined wave timings, achievable
    collective bandwidth from the psum row).  The bench *loads* any
    calibration already persisted in ``BENCH_allreduce.json`` before
    autotuning (``CostModel.register_calibration``), so backends without
    built-in constants stop falling back silently -- see
    ``CostModel.for_backend``'s logged fallback.

Every entry lands in ``BENCH_allreduce.json`` with the schema
``name -> {us_per_call, bytes, k, depth, [segments], [codec]}`` so
successive PRs can append to the perf trajectory.
``BENCH_allreduce_quick.json`` is the committed ``--quick`` twin:
``benchmarks/bench_diff.py`` gates CI against it (psum-normalized,
same-payload rows only; striped rows are gated like every other
headline engine row).

    PYTHONPATH=src python -m benchmarks.allreduce_bench
    PYTHONPATH=src python -m benchmarks.allreduce_bench --quick --out BENCH_allreduce_quick.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# 16 fake host devices; must be set before jax initializes the backend
_FORCE = "--xla_force_host_platform_device_count=16"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FORCE).strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import topologies as topo  # noqa: E402
from repro.core.collectives import (CostModel,  # noqa: E402
                                    allreduce_schedule, _best_root_probe,
                                    fused_spec_from_schedule,
                                    pipelined_spec_from_schedule,
                                    striped_spec_from_schedule,
                                    striped_tables, tree_schedule)
from repro.core.csr import tree_center  # noqa: E402
from repro.core.edst_star import star_edsts  # noqa: E402
from repro.dist.striped import (striped_allreduce,  # noqa: E402
                                tree_allgather, tree_reduce_scatter)
from repro.dist.tree_allreduce import (auto_segments,  # noqa: E402
                                       fused_tree_allreduce,
                                       per_tree_allreduce,
                                       pipelined_tree_allreduce,
                                       resolve_codec, spec_from_schedule)
from repro.launch.mesh import make_mesh  # noqa: E402

TRAJECTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "BENCH_allreduce.json")

EXEC_FABRICS = (("torus4x4", (4, 4)), ("torus2x8", (2, 8)))
SEGMENT_SWEEP = (1, 2, 4, 8)
COMPILE_FABRICS = (
    ("torus32x32", lambda: topo.device_topology((32, 32))),   # n = 1024
    ("slimfly_q7", lambda: topo.slimfly(7)),                  # n = 98
    ("polarstar_er3_qr5", lambda: topo.polarstar(3, "qr", 5)),  # n = 65
)


def _time_call(fn, iters: int) -> float:
    fn()  # warmup (compile)
    fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def _time_interleaved(fns: dict, rounds: int) -> dict:
    """Best single-call wall clock per case over ``rounds`` round-robin
    sweeps.  Interleaving one call at a time spreads host-machine drift
    over every engine alike (consecutive same-engine blocks let a slow
    patch skew one row), and the min discards contention outliers."""
    for fn in fns.values():
        fn()  # compile
        fn()
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def load_calibration(path: str = TRAJECTORY) -> None:
    """Re-register the CostModel constants a previous bench run persisted
    (``calibration/<backend>`` rows), so ``segments="auto"`` autotunes
    from measurements instead of the built-in table."""
    try:
        with open(path) as f:
            rows = json.load(f)
    except (OSError, ValueError):
        return
    for name, row in rows.items():
        if not name.startswith("calibration/"):
            continue
        consts = {k: row[k] for k in ("link_bw", "alpha", "overlap")
                  if k in row}
        if consts:
            CostModel.register_calibration(name.split("/", 1)[1], **consts)


def bench_executors(results: dict, elems: int, iters: int) -> None:
    mesh = make_mesh((16,), ("data",))
    x = (jnp.arange(16 * elems, dtype=jnp.float32).reshape(16, elems)
         * 1e-4)
    nbytes = elems * 4
    cal_alpha, cal_bw = [], []

    for label, dims in EXEC_FABRICS:
        sp = topo.device_topology(dims)
        sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
        pspec = pipelined_spec_from_schedule(sched, ("data",))
        fspec = fused_spec_from_schedule(sched, ("data",))
        lspec = spec_from_schedule(sched, ("data",))
        sspec = striped_spec_from_schedule(sched, ("data",))
        mrow = -(-elems // max(1, sched.k))
        auto_s = auto_segments(pspec, mrow)
        codec = resolve_codec()

        def jitted(body):
            f = jax.jit(jax.shard_map(
                lambda xs: body(xs.reshape(xs.shape[1:]))[None],
                mesh=mesh, in_specs=P("data"), out_specs=P("data")))
            return lambda: jax.block_until_ready(f(x))

        # zero1 step stand-in: RS grads -> elementwise owner-stripe
        # update -> AG params (full precision, like the real step); the
        # gradient allgather of the composed allreduce never runs, so
        # the row's wave count is rs+ag of the *same* stripe program
        bt = striped_tables(sspec, elems)
        z_waves = len(bt.rs_waves) + len(bt.ag_waves)

        def zero1_body(v, quantize=False):
            owned = tree_reduce_scatter(v, sspec, quantize=quantize)
            owned = owned * (0.999 / sp.n)
            return tree_allgather(owned, sspec, v.shape)

        cases = {
            "pipelined": lambda v: pipelined_tree_allreduce(v, pspec),
            "striped": lambda v: striped_allreduce(v, sspec),
            "zero1": zero1_body,
            "fused": lambda v: fused_tree_allreduce(v, fspec),
            "per_tree": lambda v: per_tree_allreduce(v, lspec),
            "psum": lambda v: jax.lax.psum(v, "data"),
        }
        if codec != "off":
            cases.update({
                "pipelined_q8": lambda v: pipelined_tree_allreduce(
                    v, pspec, quantize=True),
                "striped_q8": lambda v: striped_allreduce(v, sspec,
                                                          quantize=True),
                "zero1_q8": lambda v: zero1_body(v, quantize=True),
                "fused_q8": lambda v: fused_tree_allreduce(v, fspec,
                                                           quantize=True),
                "per_tree_q8": lambda v: per_tree_allreduce(v, lspec,
                                                            quantize=True),
            })
        # the S>1 scan issues every wave each step -- two orders of
        # magnitude slower on serialized-collective hosts (that IS the
        # datapoint) -- so the sweep times in its own group to keep the
        # headline engine rows' round-robin tight
        sweep = {f"pipelined_s{s}":
                 (lambda v, s=s: pipelined_tree_allreduce(v, pspec,
                                                          segments=s))
                 for s in SEGMENT_SWEEP}

        timed = _time_interleaved({n: jitted(b) for n, b in cases.items()},
                                  iters)
        if codec == "off":
            # the model-disabled codec compiles the IDENTICAL program as
            # f32 (resolve_codec docstring), so the q8 rows share their
            # counterpart's measurement rather than re-timing the same
            # executable into measurement noise (the striped engine's
            # allgather wire is disabled by codec="off" too)
            for eng in ("pipelined", "striped", "zero1", "fused",
                        "per_tree"):
                timed[f"{eng}_q8"] = timed[eng]
        timed.update(_time_interleaved(
            {n: jitted(b) for n, b in sweep.items()}, max(2, iters // 6)))
        cal_alpha.append(timed["pipelined"] / max(1, len(pspec.waves)))
        cal_bw.append(nbytes / max(timed["psum"], 1e-9))
        for engine, sec in timed.items():
            row = {
                "us_per_call": round(sec * 1e6, 1),
                "bytes": nbytes,
                "k": sched.k,
                "depth": 0 if engine == "psum" else sched.depth,
            }
            if engine.startswith("pipelined"):
                row["segments"] = (int(engine.rsplit("_s", 1)[1])
                                   if "_s" in engine else auto_s)
            if engine.startswith("striped"):
                row["stripes"] = sp.n
            if engine.startswith("zero1"):
                row["stripes"] = sp.n
                row["waves"] = z_waves
                row["composed_waves"] = len(bt.waves)
            if engine.endswith("_q8"):
                row["codec"] = codec
            results[f"exec/{label}/{engine}"] = row

    backend = jax.default_backend()
    row = {
        "us_per_call": round(min(cal_alpha) * 1e6, 1),
        "bytes": nbytes,
        "k": 0,
        "depth": 0,
        "alpha": min(cal_alpha),
        "link_bw": max(cal_bw),
    }
    # only the XLA host runtime's collective serialization is a KNOWN
    # property worth persisting; for other backends overlap is left to
    # CostModel's defaults rather than recorded as if it were measured
    if backend == "cpu":
        row["overlap"] = False
    results[f"calibration/{backend}"] = row


def bench_compile(results: dict, iters: int) -> None:
    for label, mk in COMPILE_FABRICS:
        sp = mk()
        g = sp.product()
        tree = sorted(g.bfs_tree(0))
        n = g.n

        csr_sec = _time_call(lambda: tree_center(n, tree), iters)
        probe_sec = _time_call(lambda: _best_root_probe(n, tree),
                               max(1, iters // 4))
        root_csr, depth_csr = tree_center(n, tree)
        assert root_csr == _best_root_probe(n, tree), label
        # full-schedule compile with the CSR center (what callers pay)
        sched_sec = _time_call(lambda: tree_schedule(n, tree), iters)

        for center, sec in (("csr_center", csr_sec),
                            ("probe_center", probe_sec),
                            ("schedule_csr", sched_sec)):
            results[f"compile/{label}/{center}"] = {
                "us_per_call": round(sec * 1e6, 1),
                "bytes": 0,
                "k": 1,
                "depth": depth_csr,
            }


def run_bench(quick: bool = False) -> dict:
    load_calibration()   # autotune from persisted measurements if present
    elems = 4096 if quick else 16384
    iters = 12 if quick else 42
    results: dict = {}
    bench_executors(results, elems, iters)
    bench_compile(results, 2 if quick else 5)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_allreduce.json")
    ap.add_argument("--quick", action="store_true",
                    help="smaller payloads / fewer iters (CI smoke)")
    args = ap.parse_args()

    results = run_bench(args.quick)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")

    width = max(len(k) for k in results)
    for name, row in results.items():
        extra = "".join(f" {key}={row[key]}"
                        for key in ("segments", "stripes", "codec")
                        if key in row)
        print(f"{name:<{width}}  {row['us_per_call']:>10.1f} us  "
              f"k={row['k']} depth={row['depth']} bytes={row['bytes']}"
              f"{extra}")
    for label, _ in EXEC_FABRICS:
        rows = {e: results[f"exec/{label}/{e}"]["us_per_call"]
                for e in ("pipelined", "pipelined_q8", "striped",
                          "striped_q8", "zero1", "zero1_q8",
                          "fused", "fused_q8",
                          "per_tree", "per_tree_q8", "psum")}
        zrow = results[f"exec/{label}/zero1"]
        print(f"{label}: fused/pipelined = "
              f"{rows['fused'] / rows['pipelined']:.2f}x   "
              f"striped/pipelined = "
              f"{rows['striped'] / rows['pipelined']:.2f}x   "
              f"psum/pipelined = {rows['psum'] / rows['pipelined']:.2f}x")
        print(f"  zero1/striped = {rows['zero1'] / rows['striped']:.2f}x  "
              f"waves {zrow['waves']} vs composed "
              f"{zrow['composed_waves']}")
        for eng in ("pipelined", "striped", "zero1", "fused", "per_tree"):
            flag = "OK" if rows[f"{eng}_q8"] <= rows[eng] else "REGRESSION"
            print(f"  {eng}_q8 vs {eng}: "
                  f"{rows[f'{eng}_q8'] / rows[eng]:.2f}x [{flag}]")
    big = "torus32x32"
    speedup = (results[f"compile/{big}/probe_center"]["us_per_call"]
               / results[f"compile/{big}/csr_center"]["us_per_call"])
    print(f"{big}: probe/csr center speedup = {speedup:.0f}x")


if __name__ == "__main__":
    main()
