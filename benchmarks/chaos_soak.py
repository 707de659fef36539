"""Chaos soak: sustained seeded faults through real train loops, with the
full detect -> classify -> escalate -> recover loop closed.

For each engine configuration this bench trains the shared quadratic toy
problem (the ZeRO-1 differential suite's problem: dense, well-scaled
gradients, uneven 53-element payload) on 16 fake host devices arranged
as a 4x4 torus DP fabric, while a seeded
:class:`repro.dist.chaos.ChaosInjector` trace drives every rung of the
recovery ladder:

  * ``dense``   -- pipelined-engine fault runtime; flap, kill,
    out-of-class burst (background ``with_rebuild`` + hot-swap),
    straggler, payload corruption, and a node loss that checkpoints
    atomically and elastically rescales onto the 8 surviving devices
    (a (2,4) torus sub-mesh);
  * ``striped`` -- reduce-scatter/allgather engine; flap, kill, burst;
  * ``zero1``   -- the sharded-optimizer step; flap, kill (with the
    ``reshard_owned`` mu/nu stripe migration on the schedule flip), and
    corruption.

Every detection tick probes the fabric BEFORE stepping (the heartbeat of
:mod:`repro.dist.health` with the injector's ``fault_mask``), so no
train step ever executes over a schedule the prober knows is dead: while
a link is suspect or a rebuild is in flight the harness stalls (the
batch index does not advance) and the committed loss sequence stays
bit-comparable to a fault-free ``psum_dp`` reference run over the SAME
batches -- the acceptance check.  Payload corruption is injected at the
telemetry boundary (a healthy host fabric cannot corrupt wires
physically); the recovery is a rollback of the just-committed step to
its pre-step snapshot and a redo, which must reconverge exactly.  The
in-graph checksum machinery itself (``telemetry=True`` ->
``replication_divergence`` / ``rs_conservation_gap``) runs live in every
step and feeds the detector alongside the injection.

A background ``with_rebuild`` holds the detection clock (the harness
polls the controller without advancing the injector) so MTTR-in-ticks
and steps-lost stay deterministic across hosts -- wall-clock MTTR
(including the repack + re-jit) is recorded separately per event.

Rows land in ``BENCH_recovery.json``:

  * ``soak/<config>/<kind>``   -- per-fault recovery: ``mttr_ticks``
    (detection ticks from first failed probe to recovery; deterministic),
    ``mttr_s`` (wall clock, informational), ``action``, ``events``;
  * ``soak/<config>/totals``   -- ``committed`` steps, ``steps_lost``,
    ``max_loss_diff`` / ``final_loss_diff`` vs the fault-free reference,
    ``unhandled_exceptions`` (must be 0), ``bw_retained``,
    ``generations``, and the full recovery ``journal``.

``benchmarks/recovery_diff.py`` gates CI on these rows against the
committed baseline (``BENCH_recovery_quick.json`` for the smoke tier).

    PYTHONPATH=src python -m benchmarks.chaos_soak
    PYTHONPATH=src python -m benchmarks.chaos_soak --quick \
        --out BENCH_recovery_quick.json
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
import numpy as np  # noqa: E402

from repro.ckpt import restore, save_checkpoint  # noqa: E402
from repro.core.collectives import CostModel  # noqa: E402
from repro.dist.chaos import ChaosInjector, make_trace  # noqa: E402
from repro.dist.health import HealthMonitor  # noqa: E402
from repro.dist.recovery import (RecoveryController,  # noqa: E402
                                 RecoveryPolicy)
from repro.dist.steps import (dp_size, fault_runtime_for_mesh,  # noqa: E402
                              make_train_step)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.optim import AdamW, ShardedAdamW, cosine_schedule  # noqa: E402
from repro.telemetry import metrics as tmetrics  # noqa: E402

MESH_ARGS = ((16, 1), ("data", "model"))
TORUS = (4, 4)
BASE_DT = 0.1            # synthetic healthy step time fed to the detector
NBYTES = 64 << 20        # bandwidth bookkeeping payload
CAUSE_TO_KIND = {"link-flap": "flap", "link-kill": "kill",
                 "link-burst": "burst", "payload-corruption": "corruption",
                 "straggler": "straggler", "node-loss": "node"}
CONFIG_KINDS = {
    "dense": ("flap", "kill", "burst", "straggler", "corruption", "node"),
    "striped": ("flap", "kill", "burst"),
    "zero1": ("flap", "kill", "corruption"),
}


class QuadAPI:
    def loss_fn(self, params, batch):
        pred = jnp.einsum("bij,ij->b", batch["x"], params["w"]) \
            + batch["x2"] @ params["b"]
        return jnp.mean((pred - batch["y"]) ** 2), {}


def make_params():
    rng = np.random.RandomState(0)
    return {"w": jnp.asarray(rng.randn(6, 8), jnp.float32) * 0.3,
            "b": jnp.asarray(rng.randn(5), jnp.float32) * 0.3}


def batch_for(i: int, rows: int = 16) -> dict:
    """Deterministic global batch for commit index ``i`` -- the soak and
    the fault-free reference consume the identical sequence."""
    rng = np.random.RandomState(1000 + i)
    return {"x": jnp.asarray(rng.randn(rows, 6, 8), jnp.float32),
            "x2": jnp.asarray(rng.randn(rows, 5), jnp.float32),
            "y": jnp.asarray(rng.randn(rows), jnp.float32)}


PSIZE = 53  # flat param count of make_params() -- the zero1 stripe payload


def _sub_torus(n: int) -> tuple:
    return {8: (2, 4), 4: (2, 2), 2: (2, 1)}[n]


def run_soak(config: str, kinds, n_ticks: int, seed: int = 0,
             ckpt_dir: str | None = None, verbose: bool = True) -> dict:
    """One soaked training run; returns the bench rows for ``config``."""
    zero1 = config == "zero1"
    engine = "pipelined" if config == "dense" else "striped"
    # baseline for the journal <-> metrics reconciliation: everything the
    # process-wide transition counter gains during THIS soak must match
    # the controller's journal exactly
    m0 = tmetrics.counter_values("edst_recovery_transitions_total")
    opt = AdamW(cosine_schedule(1e-2, 5, max(n_ticks, 20)))
    api = QuadAPI()
    cm = CostModel()

    st = {  # mutable harness state the rescale callback swaps out
        "mesh": make_mesh(*MESH_ARGS),
        "runtime": fault_runtime_for_mesh(*MESH_ARGS, TORUS, engine=engine),
        "params": make_params(),
    }
    healthy_bw = st["runtime"].effective_bandwidth(NBYTES, 0, cm)
    if zero1:
        st["opt_state"] = ShardedAdamW(opt).init_for(
            st["params"], st["runtime"], dp_size(st["mesh"]))
    else:
        st["opt_state"] = opt.init(st["params"])

    def rebuild_exec(runtime, straggler=None):
        st["runtime"] = runtime
        step = make_train_step(api, opt, st["mesh"], mode="edst",
                               fault_runtime=runtime, zero1=zero1,
                               telemetry=True)
        st["jstep"] = jax.jit(step)
        st["monitor"] = HealthMonitor(st["mesh"], runtime,
                                      straggler=straggler)

    rebuild_exec(st["runtime"])
    trace = make_trace(st["runtime"], n_ticks, seed=seed, kinds=kinds)
    inj = ChaosInjector(trace)

    commits: list = []          # committed per-step losses, in batch order
    gdiffs: list = []
    prev_snapshot = None        # state before the last committed step
    steps_lost = 0
    unhandled = 0

    def on_checkpoint():
        if ckpt_dir is not None:
            save_checkpoint(ckpt_dir, len(commits),
                            {"p": st["params"], "o": st["opt_state"]})

    def on_rescale(event):
        """Node loss: power-of-two sub-mesh over the survivors, fresh
        fault runtime on its torus, state restored from the checkpoint
        the controller just committed."""
        survivors = [v for v in range(st["runtime"].graph.n)
                     if v not in event.nodes]
        keep = 1 << int(np.log2(len(survivors)))
        if keep < 2:
            return None
        sel = survivors[:keep]
        st["mesh"] = make_mesh((keep, 1), ("data", "model"),
                               devices=[jax.devices()[v] for v in sel])
        new_rt = fault_runtime_for_mesh((keep, 1), ("data", "model"),
                                        dp_torus_shape=_sub_torus(keep),
                                        engine=engine)
        if ckpt_dir is not None:    # exercise the atomic restore path
            state, _, _ = restore(ckpt_dir,
                                  {"p": st["params"], "o": st["opt_state"]})
            st["params"], st["opt_state"] = state["p"], state["o"]
        inj.clear_fabric_state()
        return new_rt

    ctrl = RecoveryController(
        st["runtime"], RecoveryPolicy(backoff_base_s=0.01),
        on_checkpoint=on_checkpoint,
        on_rescale=on_rescale if config == "dense" else None)

    last_sync_dev = 0.0
    for tick in range(n_ticks):
        try:
            inj.advance()
            mask = inj.fault_mask(st["monitor"].plan)
            report = st["monitor"].check(
                tick, fault_mask=mask,
                step_time=BASE_DT * inj.time_dilation(),
                checksum_dev=max(inj.checksum_injection(), last_sync_dev))
            dec = ctrl.observe(report)
            # hold the detection clock while a background rebuild is in
            # flight: MTTR-in-ticks stays host-speed independent, the
            # wall clock (journal mttr_s) still records the repack cost
            waited = 0
            while dec.stall and ctrl.state == "rebuilding":
                time.sleep(0.02)
                dec = ctrl.observe(report)
                waited += 1
                if waited > 30000:
                    raise RuntimeError("background rebuild never landed")
            if dec.runtime_changed:
                rebuild_exec(ctrl.runtime,
                             straggler=st["monitor"].straggler)
            if dec.redo_step:
                # the step committed last tick went over a corrupt wire:
                # roll it back and recompute the same batch
                if prev_snapshot is not None and commits:
                    st["params"], st["opt_state"] = prev_snapshot
                    commits.pop()
                    gdiffs.pop()
                    steps_lost += 1
            elif dec.stall:
                steps_lost += 1
                if dec.backoff_s:
                    time.sleep(min(dec.backoff_s, 0.05))
                continue
            if zero1 and dec.action == "flip":
                rt, frm = ctrl.runtime, dec.detail["from_schedule"]
                s = st["opt_state"]
                st["opt_state"] = type(s)(
                    s.step,
                    rt.reshard_owned(s.mu, frm, rt.active, PSIZE),
                    rt.reshard_owned(s.nu, frm, rt.active, PSIZE))
            prev_snapshot = (st["params"], st["opt_state"])
            batch = batch_for(len(commits))
            st["params"], st["opt_state"], m = st["jstep"](
                st["params"], st["opt_state"], batch,
                jnp.int32(ctrl.schedule_id))
            commits.append(float(m["loss"]))
            gdiffs.append(float(m["grad_norm"]))
            last_sync_dev = float(m.get("sync_dev", 0.0))
        except Exception as exc:  # the soak contract: count, never crash
            unhandled += 1
            if verbose:
                print(f"[soak:{config}] UNHANDLED at tick {tick}: "
                      f"{type(exc).__name__}: {exc}")
            break

    # fault-free psum_dp reference over the identical batch sequence, on
    # the original healthy mesh
    ref_mesh = make_mesh(*MESH_ARGS)
    ref = jax.jit(make_train_step(api, opt, ref_mesh, mode="psum_dp"))
    rp, rstate = make_params(), opt.init(make_params())
    ref_losses, ref_gnorms = [], []
    for i in range(len(commits)):
        rp, rstate, rm = ref(rp, rstate, batch_for(i))
        ref_losses.append(float(rm["loss"]))
        ref_gnorms.append(float(rm["grad_norm"]))

    loss_diffs = [abs(a - b) for a, b in zip(commits, ref_losses)]
    gnorm_diffs = [abs(a - b) for a, b in zip(gdiffs, ref_gnorms)]
    final_bw = ctrl.runtime.effective_bandwidth(
        NBYTES, ctrl.runtime.active, cm)

    m1 = tmetrics.counter_values("edst_recovery_transitions_total")
    observed = {k: m1[k] - m0.get(k, 0.0) for k in m1
                if m1[k] != m0.get(k, 0.0)}
    expected: dict = {}
    for e in ctrl.journal:
        key = (("action", str(e.action)), ("cause", str(e.cause)))
        expected[key] = expected.get(key, 0.0) + 1.0
    metrics_reconciled = observed == expected

    rows = {}
    by_kind: dict = {}
    for e in ctrl.journal:
        by_kind.setdefault(CAUSE_TO_KIND[e.cause], []).append(e)
    for kind, entries in by_kind.items():
        e = entries[0]
        rows[f"soak/{config}/{kind}"] = {
            "mttr_ticks": int(e.steps_degraded),
            "mttr_s": None if e.mttr_s is None else round(e.mttr_s, 4),
            "action": e.action, "events": len(entries)}
    rows[f"soak/{config}/totals"] = {
        "ticks": n_ticks, "committed": len(commits),
        "steps_lost": steps_lost,
        "max_loss_diff": max(loss_diffs, default=0.0),
        "final_loss_diff": loss_diffs[-1] if loss_diffs else 0.0,
        "max_gnorm_diff": max(gnorm_diffs, default=0.0),
        "unhandled_exceptions": unhandled,
        "metrics_reconciled": metrics_reconciled,
        "bw_retained": round(final_bw / healthy_bw, 3),
        "generations": ctrl.generation,
        "n_final": ctrl.runtime.graph.n,
        "journal": ctrl.journal_rows()}
    if verbose:
        t = rows[f"soak/{config}/totals"]
        print(f"[soak:{config}] committed {t['committed']}/{n_ticks} ticks, "
              f"lost {t['steps_lost']}, max loss diff "
              f"{t['max_loss_diff']:.2e}, gens {t['generations']}, "
              f"unhandled {t['unhandled_exceptions']}")
        for e in ctrl.journal:
            print(f"[soak:{config}]   t={e.step} {e.cause} -> {e.action} "
                  f"(sid {e.from_schedule}->{e.to_schedule}, "
                  f"{e.steps_degraded} ticks degraded)")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: dense config only, flap+kill trace")
    ap.add_argument("--configs", default=None,
                    help="comma list from dense,striped,zero1")
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_recovery.json")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint dir for the node-loss rung "
                         "(default: a temp dir)")
    args = ap.parse_args(argv)

    if args.quick:
        plan = {"dense": ("flap", "kill")}
        default_ticks = 16
    else:
        plan = {c: CONFIG_KINDS[c] for c in
                (args.configs.split(",") if args.configs
                 else ("dense", "striped", "zero1"))}
        default_ticks = None
    n_ticks = args.ticks or default_ticks

    import tempfile
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="chaos_soak_ckpt_")
    results = {}
    failed = 0
    for config, kinds in plan.items():
        ticks = n_ticks or (48 if len(kinds) > 3 else 24)
        rows = run_soak(config, kinds, ticks, seed=args.seed,
                        ckpt_dir=os.path.join(ckpt_dir, config))
        results.update(rows)
        totals = rows[f"soak/{config}/totals"]
        if (totals["unhandled_exceptions"] or totals["max_loss_diff"] > 1e-3
                or not totals["metrics_reconciled"]):
            failed += 1

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[soak] wrote {len(results)} rows to {args.out}")
    if failed:
        print(f"[soak] FAILED: {failed} config(s) diverged or crashed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
