"""Telemetry cost + fidelity bench: scope overhead pairs, per-wave
measured-vs-predicted residuals, and the fitted calibration row.

Three row families land in ``BENCH_telemetry.json``:

  * ``telemetry/<fabric>/<engine>/plain`` and ``.../scoped`` -- the SAME
    jitted allreduce timed with the executors' ``edst/t*/w*/op`` named
    scopes disabled vs enabled, interleaved in one round-robin so host
    drift hits both alike.  ``jax.named_scope`` is trace-time HLO
    metadata (the compiled executable is identical), so the pair must
    agree to measurement noise; CI gates ``scoped/plain <= 1.05`` via
    ``python -m benchmarks.bench_diff --overhead``.
  * ``waves/<fabric>/<engine>`` -- the wave-by-wave instrumented
    executor (:func:`repro.telemetry.timing.wave_report`): per-wave
    measured times (block-until-ready per wave, best of iters) against
    the CostModel's per-wave predictions, with residuals.
  * ``calibration/<backend>`` -- ``t = alpha + bytes/link_bw`` fitted
    from every measured wave and fed back into the registry
    ``CostModel.for_backend`` consults (the measured-calibration loop).

Runs on 16 fake host devices; absolute numbers are host-collective
latencies, only the plain/scoped ratio and the residual STRUCTURE are
meaningful off real fabrics.

    python -m benchmarks.telemetry_bench --out BENCH_telemetry.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

_FORCE = "--xla_force_host_platform_device_count=16"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _FORCE).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import topologies as topo  # noqa: E402
from repro.core.collectives import (allreduce_schedule,  # noqa: E402
                                    pipelined_spec_from_schedule,
                                    striped_spec_from_schedule)
from repro.core.edst_star import star_edsts  # noqa: E402
from repro.dist.striped import striped_allreduce  # noqa: E402
from repro.dist.tree_allreduce import (pipelined_tree_allreduce,  # noqa: E402
                                       set_wave_scopes)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.telemetry import timing  # noqa: E402

FABRICS = (("torus4x4", (4, 4)), ("torus2x8", (2, 8)))
ENGINES = ("pipelined", "striped")
DEFAULT_ELEMS = 1 << 20          # 4 MiB of f32 -- the trace default


def _specs(dims):
    sp = topo.device_topology(dims)
    sched = allreduce_schedule(sp.n, star_edsts(sp).trees)
    return {"pipelined": pipelined_spec_from_schedule(sched, ("data",)),
            "striped": striped_spec_from_schedule(sched, ("data",))}


def _jitted(body, mesh, x):
    f = jax.jit(jax.shard_map(
        lambda xs: body(xs.reshape(xs.shape[1:]))[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    return lambda: jax.block_until_ready(f(x))


def _paired(fns: dict, rounds: int) -> dict:
    """Best single-call wall clock per case, round-robin interleaved (the
    allreduce_bench discipline: drift lands on every case alike)."""
    for fn in fns.values():
        fn()   # compile
        fn()
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def bench_overhead(results: dict, elems: int, iters: int) -> None:
    """plain/scoped pairs per fabric x engine.  The scope toggle flips a
    module flag read at TRACE time, so each arm jits its own callable
    under the matching flag state and both executables are compiled
    before any timed call."""
    mesh = make_mesh((16,), ("data",))
    x = (jnp.arange(16 * elems, dtype=jnp.float32).reshape(16, elems)
         * 1e-4)
    nbytes = elems * 4
    for label, dims in FABRICS:
        specs = _specs(dims)
        bodies = {
            "pipelined": lambda v: pipelined_tree_allreduce(
                v, specs["pipelined"]),
            "striped": lambda v: striped_allreduce(v, specs["striped"]),
        }
        fns = {}
        for eng, body in bodies.items():
            prev = set_wave_scopes(False)
            try:
                fns[f"{eng}/plain"] = _jitted(body, mesh, x)
                fns[f"{eng}/plain"]()          # compile under scopes-off
                set_wave_scopes(True)
                fns[f"{eng}/scoped"] = _jitted(body, mesh, x)
                fns[f"{eng}/scoped"]()         # compile under scopes-on
            finally:
                set_wave_scopes(prev)
        timed = _paired(fns, iters)
        for name, sec in timed.items():
            eng = name.split("/")[0]
            results[f"telemetry/{label}/{name}"] = {
                "us_per_call": round(sec * 1e6, 1),
                "bytes": nbytes,
                "waves": len(specs[eng].waves),
            }


def bench_waves(results: dict, elems: int, iters: int) -> None:
    """Wave-by-wave measured-vs-predicted rows + the fitted calibration
    fed back into the CostModel registry."""
    mesh = make_mesh((16,), ("data",))
    nbytes = elems * 4
    all_wires, all_meas = [], []
    for label, dims in FABRICS:
        specs = _specs(dims)
        for eng in ENGINES:
            rep = timing.wave_report(specs[eng], nbytes, iters=iters,
                                     mesh=mesh)
            results[f"waves/{label}/{eng}"] = rep
            all_wires.extend(rep["wire_bytes"])
            all_meas.extend(t * 1e-6 for t in rep["measured_us"])
    cal = timing.register_measured(all_wires, all_meas)
    results[f"calibration/{cal['backend']}"] = {
        "alpha": cal["alpha"], "link_bw": cal["link_bw"],
        "samples": len(all_wires),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_telemetry.json")
    ap.add_argument("--elems", type=int, default=DEFAULT_ELEMS)
    ap.add_argument("--iters", type=int, default=30,
                    help="round-robin rounds for the overhead pairs")
    ap.add_argument("--wave-iters", type=int, default=5,
                    help="best-of iterations per instrumented wave")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: smaller payload, fewer rounds")
    args = ap.parse_args(argv)
    if args.quick:
        args.elems = min(args.elems, 1 << 16)
        args.iters = min(args.iters, 8)
        args.wave_iters = min(args.wave_iters, 3)

    results: dict = {}
    bench_overhead(results, args.elems, args.iters)
    bench_waves(results, args.elems, args.wave_iters)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")

    width = max(len(n) for n in results)
    for name in sorted(results):
        row = results[name]
        if "us_per_call" in row:
            print(f"{name:<{width}}  {row['us_per_call']:>10.1f} us")
        elif name.startswith("waves/"):
            s = row["summary"]
            print(f"{name:<{width}}  measured {s['measured_total_us']:>10.1f}"
                  f" us  predicted {s['predicted_total_us']:>10.1f} us  "
                  f"mean|resid| {s['mean_abs_residual_us']:.1f} us")
        else:
            print(f"{name:<{width}}  alpha {row['alpha']:.2e} s  "
                  f"link_bw {row['link_bw']:.3g} B/s")
    print(f"\nwrote {len(results)} rows to {args.out}")

    bad = []
    for label, _ in FABRICS:
        for eng in ENGINES:
            p = results[f"telemetry/{label}/{eng}/plain"]["us_per_call"]
            s = results[f"telemetry/{label}/{eng}/scoped"]["us_per_call"]
            if p > 0 and s / p > 1.05:
                bad.append(f"telemetry/{label}/{eng}: {s / p:.3f}x")
    if bad:
        print("scope overhead above 1.05x (named_scope must be free):")
        for b in bad:
            print(f"  {b}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
