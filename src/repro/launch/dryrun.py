import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with ShapeDtypeStruct stand-ins (no allocation).

For each cell this prints/records:
  * compiled.memory_analysis()  -- proves the cell fits per-device HBM;
  * compiled.cost_analysis()    -- HLO FLOPs / bytes for the roofline;
  * collective-op bytes parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute) -- the
    roofline's collective term.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.dist import sharding as shd
from repro.dist.steps import make_train_step
from repro.launch.mesh import make_production_mesh
from repro.models.api import build
from repro.optim import AdamW, cosine_schedule

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in optimized HLO."""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)", stripped)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for c in COLLECTIVES:
            if re.search(rf"\b{c}(?:-start|-done)?\(", rhs):
                op = c
                break
        if op is None or f"{op}-done(" in rhs:
            continue  # count the -start, skip the -done (same buffer)
        head = rhs.split(f" {op}", 1)[0]
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(head):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _shapes_and_axes(fn, *args):
    """eval_shape that also captures the (static) logical-axes side output."""
    box = {}

    def wrapper(*a):
        out, axes = fn(*a)
        box["axes"] = axes
        return out

    shapes = jax.eval_shape(wrapper, *args)
    return shapes, box["axes"]


def build_cell(arch: str, shape_name: str, mesh, sync_mode: str = "gspmd",
               fsdp: bool = True, cfg_overrides: dict | None = None):
    """Returns (step_fn, in_shapes tuple, in_shardings tuple)."""
    import dataclasses
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = cfg.shape(shape_name)
    if shape.kind != "train" and cfg.serve_q_block and not cfg_overrides:
        # serve-time attention blocks (§Perf hillclimb 1)
        cfg = dataclasses.replace(cfg, q_block=cfg.serve_q_block,
                                  kv_block=cfg.serve_kv_block)
    if shape.kind == "decode" and shape.global_batch >= 16:
        # weights stay TP-resident at serve time (§Perf hillclimb 2).
        # batch-1 ultra-long decode is the exception: it streams the whole
        # weight shard per token, so ZeRO-3 sharding (smaller local reads +
        # gather) wins -- measured on rwkv6 long_500k (12x memory-term hit
        # with resident weights).
        fsdp = False
    api = build(cfg)
    key = jax.random.PRNGKey(0)

    pshapes, paxes = _shapes_and_axes(lambda k: api.init(k), key)
    pshard = shd.tree_shardings(paxes, pshapes, mesh, fsdp=fsdp)

    batch_shapes = api.input_specs(shape)
    batch_axes = api.batch_axes(shape)
    bshard = {k: jax.sharding.NamedSharding(
                  mesh, shd.spec_for(batch_axes[k], v.shape, mesh, fsdp=False))
              for k, v in batch_shapes.items()}

    if shape.kind == "train":
        opt = AdamW(cosine_schedule(3e-4, 100, 10_000))
        oshapes = jax.eval_shape(opt.init, pshapes)
        pshard, oshard = shd.train_state_shardings(paxes, pshapes, mesh,
                                                   fsdp=fsdp)
        step_fn = make_train_step(api, opt, mesh, mode=sync_mode, fsdp=fsdp)
        return step_fn, (pshapes, oshapes, batch_shapes), \
            (pshard, oshard, bshard)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return api.prefill_fn(params, batch)
        return prefill_step, (pshapes, batch_shapes), (pshard, bshard)

    # decode
    cshapes, caxes = _shapes_and_axes(
        lambda: api.init_cache(shape.global_batch, shape.seq_len))
    cshard = shd.tree_shardings(caxes, cshapes, mesh, fsdp=False)

    def decode_step(params, caches, batch):
        return api.decode_fn(params, caches, batch)

    return decode_step, (pshapes, cshapes, batch_shapes), \
        (pshard, cshard, bshard)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             sync_mode: str = "gspmd", fsdp: bool = True,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    step_fn, in_shapes, in_shardings = build_cell(arch, shape_name, mesh,
                                                  sync_mode, fsdp)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=in_shardings)
        lowered = jitted.lower(*in_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    from repro.analysis.hlo import analyze_hlo
    loop_aware = analyze_hlo(hlo_text)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "sync": sync_mode, "fsdp": fsdp,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collectives": coll,
        "loop_aware": {
            "dot_flops": loop_aware.dot_flops,
            "bytes_touched": loop_aware.bytes_touched,
            "collective_bytes": loop_aware.collective_bytes,
            "collective_counts": loop_aware.collective_counts,
            "total_collective_bytes": loop_aware.total_collective_bytes,
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {result['mesh']} "
              f"({sync_mode}): OK  lower={t_lower:.1f}s "
              f"compile={t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={result['flops']:.3e} "
              f"bytes={result['bytes_accessed']:.3e}")
        print(f"  collective bytes: {coll['total_bytes']:.3e} "
              f"{coll['counts']}")
    return result


def iter_cells():
    for name, cfg in configs.ARCHS.items():
        for shape in configs.LM_SHAPES:
            if shape.name in cfg.skip_shapes:
                yield name, shape.name, True
            else:
                yield name, shape.name, False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sync", default="gspmd",
                    choices=["gspmd", "edst", "psum_dp"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    results = []
    if args.all:
        cells = list(iter_cells())
    else:
        cells = [(args.arch, args.shape, False)]
    for arch, shape_name, skipped in cells:
        if skipped:
            cfg = configs.get(arch)
            results.append({"arch": arch, "shape": shape_name,
                            "mesh": "2x16x16" if args.multi_pod else "16x16",
                            "skipped": True, "reason": cfg.skip_reason})
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({cfg.skip_reason})")
            continue
        try:
            results.append(run_cell(arch, shape_name, args.multi_pod,
                                    args.sync, not args.no_fsdp))
        except Exception as e:  # noqa: BLE001 -- report and continue the sweep
            traceback.print_exc()
            results.append({"arch": arch, "shape": shape_name,
                            "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    failed = [r for r in results if "error" in r]
    print(f"[dryrun] done: {len(results) - len(failed)}/{len(results)} OK")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
