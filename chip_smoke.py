#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # EDST gradient sync on four chips

One chip (the default): train smollm-135m at its published widths
through ``repro.launch.train.main`` for 4 steps (batch 8, seq 2048) and
require finite losses starting near ln(vocab); then run every Pallas
kernel of the main path on the chip at real sizes -- the tree combine,
the three int8 wire-codec kernels and flash attention -- and compare
each with its jnp reference.

Four chips (``--four-chips``): train the same model on a (4, 1) data-
parallel mesh (batch 32, seq 2048, 3 steps, one seed) under ``--sync
psum_dp`` (the reference), ``--sync edst`` and ``--sync edst
--quantize-grads``, compare their losses and step-0 gradient norms, and
check the compiled EDST steps for ``collective-permute`` waves and, with
the int8 wire, Pallas kernels.

All work runs in this one process, which holds the chip.  Step times
and peak memory are printed as one-off information, not as a benchmark.
Without a TPU the script exits non-zero and prints no result.  The last
line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every check passed.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# XLA's dump of the train steps the four-chip phase compiles
HLO_DUMP = ROOT / ".xla_dump"

ARCH = "smollm-135m"
VOCAB = 49152
ONE_CHIP_TRAIN = ["--arch", ARCH, "--mesh", "1,1", "--steps", "4",
                  "--batch", "8", "--seq", "2048"]
FOUR_CHIP_TRAIN = ["--arch", ARCH, "--mesh", "4,1", "--steps", "3",
                   "--batch", "32", "--seq", "2048", "--seed", "0"]
FOUR_CHIP_MODES = {"psum_dp": ["--sync", "psum_dp"],
                   "edst": ["--sync", "edst"],
                   "edst_q8": ["--sync", "edst", "--quantize-grads"]}

# Random init predicts close to uniformly: the first loss is ln(vocab)
# plus half the variance of the initial logits.
FIRST_LOSS_BAND = 0.5
# edst sums the same f32 gradients as psum in another order; the CPU
# rehearsal (4 devices, reduced widths) agreed to 0 in the loss and to
# the printed digit in the gradient norm.  A sync that dropped or
# doubled a device's share moves the step-1 loss by ~1e-2 here.
EDST_LOSS_TOL = 1e-3
EDST_GNORM_RTOL = 1e-3
# The int8 wire rounds each gradient element to a step of 1/127 of its
# chunk's max |g|, so elements far below the max arrive as 0.  With the
# wire forced on, the CPU rehearsal (4 devices, reduced widths) moved
# the loss by 3.15e-5 and the step-0 gradient norm by 0.47%.  The full-
# width gradient is ~480x larger (134.5M against 279k elements), so a
# chunk holds far more elements under one scale and more round to 0; at
# the warm-up learning rates of steps 0-1 (1.5e-5, 3e-5) that still
# moves the loss by far less than 1% of ln(vocab), while a wire that
# sent garbage or NaN leaves this band.  How far the int8 gradient norm
# strays at full width is printed, not checked.
Q8_LOSS_TOL = 1e-1

# one tree's share of a smollm-135m gradient (134.5M f32 over four
# devices), odd so the kernels' last grid block runs past the end
GRAD_ELEMS = 33_750_017
FLASH_SHAPE = (8, 2048, 9, 3, 64)   # batch, seq, heads, kv heads, head dim

_LOG = re.compile(r"\[train\] step\s+(\d+) loss (\S+) gnorm (\S+) lr \S+ "
                  r"\((\S+)s\)")


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def run_train(argv):
    """``repro.launch.train.main(argv)`` with its log echoed and parsed:
    (losses, step-0 grad norm, wall seconds of each step)."""
    from repro.launch.train import main as train_main
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        losses = train_main(argv + ["--log-every", "1"])
    rows = [m.groups() for m in _LOG.finditer(buf.getvalue())]
    gnorm0 = float(rows[0][2])
    ends = [float(r[3]) for r in rows]
    secs = [b - a for a, b in zip([0.0] + ends, ends)]
    return losses, gnorm0, secs


def check(checks, name, ok, detail):
    checks.append((name, bool(ok)))
    print(f"[{'ok' if ok else 'FAIL'}] {name}: {detail}")


def _device_label():
    import jax
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def _info(what):
    print(f"[info, one-off, not a benchmark] {_device_label()}: {what}")


def train_one_chip(checks):
    import jax
    losses, _, secs = run_train(ONE_CHIP_TRAIN)
    finite = all(math.isfinite(x) for x in losses)
    check(checks, "train: finite losses", finite and len(losses) == 4,
          f"losses {losses}")
    first = losses[0] if losses else float("nan")
    check(checks, "train: first loss near ln(vocab)",
          abs(first - math.log(VOCAB)) < FIRST_LOSS_BAND,
          f"{first:.4f} vs ln({VOCAB}) = {math.log(VOCAB):.4f} "
          f"(band {FIRST_LOSS_BAND})")
    _info("seconds per step, step 0 compiles: "
          + ", ".join(f"{s:.3f}" for s in secs))
    stats = jax.devices()[0].memory_stats() or {}
    _info(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")


def _run_kernel(fn, *args, **static):
    """Compile ``fn`` for the device, run it; (output, has Pallas call)."""
    compiled = fn.lower(*args, **static).compile()
    return compiled(*args), "tpu_custom_call" in compiled.as_text()


def _compare(checks, name, out, ref, pallas, atol=0.0, rtol=0.0):
    import jax.numpy as jnp
    check(checks, f"{name}: Pallas kernel in the compiled HLO", pallas,
          "tpu_custom_call" + ("" if pallas else " missing"))
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    diff = jnp.abs(out - ref)
    bad = int(jnp.sum(diff > atol + rtol * jnp.abs(ref)))
    check(checks, f"{name}: matches its jnp reference",
          out.shape == ref.shape and bad == 0,
          f"shape {tuple(out.shape)}, {int(jnp.sum(diff > 0))} elements "
          f"differ, {bad} outside atol {atol:.3g} rtol {rtol}, "
          f"max |diff| {float(jnp.max(diff)):.3g}")


def kernels_one_chip(checks):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.tree_combine.kernel import (q8_combine_wire,
                                                   q8_pack_wire,
                                                   q8_unpack_wire,
                                                   tree_combine)
    from repro.kernels.tree_combine.ref import (q8_combine_ref, q8_pack_ref,
                                                q8_scale, q8_unpack_ref,
                                                tree_combine_ref)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    n = GRAD_ELEMS
    x = jax.random.normal(k1, (n,), jnp.float32) * 3.3
    part = jax.random.normal(k2, (n,), jnp.float32)
    recv = jax.random.normal(k3, (1, n), jnp.float32)
    scale = jax.jit(q8_scale)(x)

    out, pallas = _run_kernel(tree_combine, recv, part)
    _compare(checks, f"tree_combine ({n} f32)", out,
             jax.jit(tree_combine_ref)(recv, part), pallas)
    wire, pallas = _run_kernel(q8_pack_wire, x, scale)
    # the kernel multiplies by a reciprocal of the scale that XLA computes
    # in a fusion of its own; one ulp there moves a lane that sits on a
    # rounding boundary by one int8 step
    _compare(checks, f"q8_pack_wire ({n} f32)", wire,
             jax.jit(q8_pack_ref)(x, scale), pallas, atol=1.0)
    # both decoders read the kernel's wire, as the sync does
    out, pallas = _run_kernel(q8_combine_wire, wire, part)
    # partial + lanes * scale, rounded once (a fused multiply-add) or twice
    fma_atol = 2 * float(jnp.finfo(jnp.float32).eps) * float(
        jnp.max(jnp.abs(x)) + jnp.max(jnp.abs(part)))
    _compare(checks, "q8_combine_wire", out,
             jax.jit(q8_combine_ref)(wire, part), pallas, atol=fma_atol)
    out, pallas = _run_kernel(q8_unpack_wire, wire)
    _compare(checks, "q8_unpack_wire", out, jax.jit(q8_unpack_ref)(wire),
             pallas)

    b, s, h, kv, d = FLASH_SHAPE
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (b, s, kv, d), jnp.bfloat16)
    out, pallas = _run_kernel(flash_attention, q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(attention_ref)(q, k, v)
    # bf16 keeps 8 significant bits: a few roundings apart is ~2e-2
    _compare(checks, f"flash_attention {FLASH_SHAPE} bf16", out, ref,
             pallas, atol=2e-2, rtol=2e-2)


def _new_step_hlo(before):
    """Text of the optimized train-step HLO dumped since ``before``."""
    files = sorted(set(HLO_DUMP.glob("*jit_step*after_optimizations.txt"))
                   - before)
    return "".join(f.read_text() for f in files)


def sync_four_chips(checks):
    import jax
    # the HLO checks read XLA's dump of what this process compiles, so
    # nothing may come from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    runs = {}
    for label, flags in FOUR_CHIP_MODES.items():
        before = set(HLO_DUMP.glob("*"))
        losses, gnorm0, secs = run_train(FOUR_CHIP_TRAIN + flags)
        runs[label] = (losses, gnorm0, _new_step_hlo(before))
        _info(f"{label} seconds per step, step 0 compiles: "
              + ", ".join(f"{s:.3f}" for s in secs))
    ref_losses, ref_g, _ = runs["psum_dp"]
    check(checks, "psum_dp: finite losses",
          all(math.isfinite(x) for x in ref_losses), f"{ref_losses}")
    for label, ltol in (("edst", EDST_LOSS_TOL), ("edst_q8", Q8_LOSS_TOL)):
        losses, g, hlo = runs[label]
        diff = max(abs(a - b) for a, b in zip(losses, ref_losses))
        check(checks, f"{label}: losses match psum_dp",
              len(losses) == len(ref_losses) and diff <= ltol,
              f"{losses} vs {ref_losses}, max |diff| {diff:.3g} "
              f"(tol {ltol})")
        rel = abs(g - ref_g) / ref_g
        if label == "edst":
            check(checks, f"{label}: step-0 grad norm matches psum_dp",
                  rel <= EDST_GNORM_RTOL,
                  f"{g} vs {ref_g} (rtol {EDST_GNORM_RTOL})")
        else:
            _info(f"{label} step-0 grad norm {g} vs psum_dp {ref_g}, "
                  f"relative difference {rel:.3g}")
        ops = {op: hlo.count(op) for op in ("collective-permute",
                                            "tpu_custom_call")}
        want = ["collective-permute"] + (["tpu_custom_call"]
                                         if label == "edst_q8" else [])
        check(checks, f"{label}: compiled step ops",
              all(ops[op] > 0 for op in want), f"{ops}, need {want}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EDST-vs-psum_dp sync phase on four "
                         "chips")
    args = ap.parse_args(argv)
    if args.four_chips:
        shutil.rmtree(HLO_DUMP, ignore_errors=True)
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"), f"--xla_dump_to={HLO_DUMP}",
            "--xla_dump_hlo_as_text", "--xla_dump_hlo_module_re=jit_step"]))

    import jax
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
                 f"{devices[0].platform} device(s)); nothing was run")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} TPU chips, found {len(devices)}")
    print(f"chip_smoke: {_device_label()}, compile cache "
          f"{enable_compile_cache()}")

    checks = []
    if args.four_chips:
        sync_four_chips(checks)
    else:
        train_one_chip(checks)
        kernels_one_chip(checks)
    failed = [name for name, ok in checks if not ok]
    if failed:
        sys.exit(f"chip_smoke: {len(failed)} check(s) failed: {failed}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
