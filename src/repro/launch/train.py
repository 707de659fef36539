"""End-to-end training driver.

Composes: config -> model -> sharded train step (gspmd | edst | psum_dp
gradient sync) -> deterministic data stream -> checkpoint/restart -> fault
events.  Runs on whatever devices exist (CPU smoke: --mesh 1,1); the
production launch passes --mesh 16,16 (or 2,16,16 with pod axis) on real
slices.

    python -m repro.launch.train --arch smollm-135m --steps 300 \
        --batch 8 --seq 256 --mesh 1,1 --sync edst --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import (latest_step, restore, restore_sharded,
                        save_checkpoint, save_sharded_checkpoint)
from repro.core.collectives import owner_element_map
from repro.data import SyntheticLMStream
from repro.dist import sharding as shd
from repro.dist.steps import dp_size, edst_spec_for_mesh, make_train_step
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.api import build
from repro.optim import AdamW, ShardedAdamW, cosine_schedule
from repro.optim.adamw import OptState


def parse_mesh(s: str):
    dims = tuple(int(x) for x in s.split(","))
    names = ("pod", "data", "model")[-len(dims):]
    return dims, names


def _save(args, step, params, opt_state, zmap):
    if args.zero1:
        psize = sum(int(np.prod(p.shape, dtype=np.int64))
                    for p in jax.tree.leaves(params))
        save_sharded_checkpoint(args.ckpt_dir, step, params, opt_state,
                                zmap, psize)
    else:
        save_checkpoint(args.ckpt_dir, step, {"p": params, "o": opt_state})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--sync", default="gspmd",
                    choices=["gspmd", "edst", "psum_dp"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize-grads", action="store_true")
    ap.add_argument("--edst-engine", default="pipelined",
                    choices=["pipelined", "striped", "fused"],
                    help="compiled allreduce form for --sync edst")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: reduce-scatter grads, owner-stripe "
                         "AdamW, allgather params (forces --sync edst "
                         "--edst-engine striped)")
    ap.add_argument("--recover", action="store_true",
                    help="close the fault loop (--sync edst): heartbeat-"
                         "probe the fabric each step, feed step-time and "
                         "gradient-checksum telemetry to the recovery "
                         "controller, and recover in place -- retry on "
                         "flaps, schedule-id flip on link kills, "
                         "background rebuild + hot-swap on bursts; node "
                         "loss checkpoints and exits (rescale by "
                         "relaunching on the surviving mesh)")
    ap.add_argument("--trace-out", default=None,
                    help="write a predicted Perfetto trace (Chrome trace "
                         "event JSON) of the compiled sync program at this "
                         "run's gradient payload size before training "
                         "starts (--sync edst; with --recover the whole "
                         "fault-runtime entry table is rendered)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a JAX profiler trace of the training "
                         "loop into DIR: host spans train/{input,dispatch,"
                         "readback,checkpoint,recovery} inside one "
                         "'train' step span each, and device ops labelled "
                         "by the step's step/*, model/* and "
                         "edst/t*/w*/op named scopes")
    ap.add_argument("--metrics-out", default=None,
                    help="dump the telemetry metrics registry (JSON) at "
                         "the end of the run")
    ap.add_argument("--journal-out", default=None,
                    help="append the recovery journal to this JSONL file "
                         "as transitions happen (--recover)")
    args = ap.parse_args(argv)
    if args.zero1:
        args.sync, args.edst_engine = "edst", "striped"
    if args.recover and (args.sync != "edst" or args.zero1):
        ap.error("--recover requires --sync edst without --zero1 (the "
                 "zero1 recovery loop lives in benchmarks/chaos_soak.py)")

    enable_compile_cache()
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = build(cfg)
    dims, names = parse_mesh(args.mesh)
    mesh = make_mesh(dims, names)
    opt = AdamW(cosine_schedule(args.lr, args.warmup, args.steps))

    key = jax.random.PRNGKey(args.seed)
    with jax.set_mesh(mesh):
        params, axes = api.init(key)
        pshard, oshard = shd.train_state_shardings(axes, params, mesh)
        params = jax.device_put(params, pshard)
        zspec = zmap = None
        if args.zero1:
            zspec = edst_spec_for_mesh(dims, names, engine="striped")
            psize = sum(int(np.prod(p.shape, dtype=np.int64))
                        for p in jax.tree.leaves(params))
            zmap = owner_element_map(zspec, psize)
            opt_state = ShardedAdamW(opt).init_for(
                params, zspec, dp_size(mesh))
            oshard = shd.zero1_state_shardings(opt_state, mesh)
        else:
            opt_state = opt.init(params)
        opt_state = jax.device_put(opt_state, oshard)

        runtime = monitor = ctrl = None
        if args.recover and dp_size(mesh) > 1:
            from repro.dist.health import HealthMonitor
            from repro.dist.recovery import RecoveryController
            from repro.dist.steps import fault_runtime_for_mesh
            runtime = fault_runtime_for_mesh(dims, names,
                                             engine=args.edst_engine)
            monitor = HealthMonitor(mesh, runtime)
            ctrl = RecoveryController(runtime, journal_path=args.journal_out)

        step_fn = make_train_step(api, opt, mesh, mode=args.sync,
                                  quantize=args.quantize_grads,
                                  engine=args.edst_engine,
                                  zero1=args.zero1,
                                  fault_runtime=runtime,
                                  telemetry=runtime is not None)
        # rollback on a suspect step needs the pre-step buffers alive
        donate = () if ctrl is not None else (0, 1)
        # the new state comes back in the layout it went in with, so every
        # step after the first reuses the first step's executable
        out_shardings = (pshard, oshard, None)
        jstep = jax.jit(step_fn, donate_argnums=donate,
                        out_shardings=out_shardings)

        if args.trace_out:
            if args.sync != "edst" or dp_size(mesh) < 2:
                print("[train] --trace-out skipped: no compiled EDST sync "
                      "program on this mesh/sync mode")
            else:
                from repro.telemetry import trace as ttrace
                psize = sum(int(np.prod(p.shape, dtype=np.int64))
                            for p in jax.tree.leaves(params))
                if runtime is not None:
                    tr = ttrace.trace_runtime(runtime, nbytes=4 * psize)
                else:
                    spec = (zspec if zspec is not None else
                            edst_spec_for_mesh(dims, names,
                                               engine=args.edst_engine))
                    tr = ttrace.trace_spec(spec, nbytes=4 * psize,
                                           label=f"edst/{args.edst_engine}")
                ttrace.write_trace(args.trace_out, tr)
                print(f"[train] predicted sync trace -> {args.trace_out}")

        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            if args.zero1:
                params, opt_state, start, extra = restore_sharded(
                    args.ckpt_dir, params, zmap, param_shardings=pshard,
                    state_shardings=oshard)
            else:
                state, start, extra = restore(
                    args.ckpt_dir, {"p": params, "o": opt_state},
                    shardings={"p": pshard, "o": oshard})
                params, opt_state = state["p"], state["o"]
            print(f"[train] resumed from step {start}")

        stream = SyntheticLMStream(cfg.vocab, args.seq, args.batch,
                                   seed=args.seed)
        from repro.telemetry import metrics as tmetrics
        steps_total = tmetrics.counter(
            "edst_train_steps_total", "optimizer steps committed, by sync mode")
        if args.profile_dir:
            jax.profiler.start_trace(args.profile_dir)
        t0 = time.time()
        losses = []
        step = start
        # host spans, on the profiler's clock with the device ops: each
        # idle gap of a --profile-dir trace falls in one of them
        ann = jax.profiler.TraceAnnotation
        while step < args.steps:
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with ann("train/input"):
                    batch = {"tokens": jnp.asarray(stream.batch(step))}
                if ctrl is not None:
                    snapshot = (params, opt_state)
                    t1 = time.time()
                    with ann("train/dispatch"):
                        params, opt_state, metrics = jstep(
                            params, opt_state, batch,
                            jnp.int32(ctrl.schedule_id))
                    with ann("train/readback"):
                        # blocks: dt is the real step
                        loss = float(metrics["loss"])
                    with ann("train/recovery"):
                        report = monitor.check(
                            step, step_time=time.time() - t1,
                            checksum_dev=float(metrics.get("sync_dev", 0.0)))
                        dec = ctrl.observe(report)
                    if dec.action == "rescale" and ctrl.state == "stalled":
                        # a lost node needs a NEW process mesh: checkpoint
                        # and hand off to repro.launch.elastic on the
                        # survivors
                        params, opt_state = snapshot
                        if args.ckpt_dir:
                            with ann("train/checkpoint"):
                                _save(args, step, params, opt_state, zmap)
                        print(f"[train] node loss at step {step} "
                              f"({dec.detail.get('nodes')}); checkpoint "
                              "saved -- relaunch on the surviving mesh "
                              "(repro.launch.elastic)")
                        break
                    if dec.action != "none":
                        # the step ran over suspect fabric: discard and
                        # redo after recovery (flip / hot-swap / backoff)
                        params, opt_state = snapshot
                        print(f"[train] step {step}: {dec.action} "
                              f"(schedule {dec.schedule_id}) {dec.detail}")
                        with ann("train/recovery"):
                            if dec.runtime_changed:
                                from repro.dist.health import HealthMonitor
                                step_fn = make_train_step(
                                    api, opt, mesh, mode=args.sync,
                                    quantize=args.quantize_grads,
                                    engine=args.edst_engine,
                                    fault_runtime=ctrl.runtime,
                                    telemetry=True)
                                jstep = jax.jit(step_fn,
                                                out_shardings=out_shardings)
                                monitor = HealthMonitor(
                                    mesh, ctrl.runtime,
                                    straggler=monitor.straggler)
                            if dec.backoff_s:
                                time.sleep(dec.backoff_s)
                        continue
                else:
                    with ann("train/dispatch"):
                        params, opt_state, metrics = jstep(params, opt_state,
                                                           batch)
                    with ann("train/readback"):
                        loss = float(metrics["loss"])
                losses.append(loss)
                steps_total.inc(mode=args.sync)
                if step % args.log_every == 0 or step == args.steps - 1:
                    dt = time.time() - t0
                    print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.6g} "
                          f"lr {float(metrics['lr']):.2e} ({dt:.3f}s)")
                if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                    with ann("train/checkpoint"):
                        _save(args, step + 1, params, opt_state, zmap)
                step += 1
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"[train] profiler trace -> {args.profile_dir}")
        if args.metrics_out:
            tmetrics.REGISTRY.dump_json(args.metrics_out)
            print(f"[train] metrics -> {args.metrics_out}")
        if ctrl is not None and ctrl.journal:
            print(f"[train] recovery journal ({len(ctrl.journal)} entries):")
            for row in ctrl.journal_rows():
                print(f"[train]   {json.dumps(row)}")
        if args.ckpt_dir:
            _save(args, args.steps, params, opt_state, zmap)
    print(f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
