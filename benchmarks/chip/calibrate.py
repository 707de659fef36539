#!/usr/bin/env python3
"""Readings from which a cell's limits are set, on the chip, in one
process (one build and compile of the step for every seed).

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 11,12,... [--faults 3] [--out FILE]

For every seed: the program's first steps through the harness's own
set-up (weights from the seed, the window's loop and feed), then the
plain float32 reference, and the compared numbers of the program
against it (the lower readings).  For the first ``--faults`` seeds
also: the control (the reference computed in float8, put in the
program's place), half of every batch left out, and, on a mesh with
more than one data-parallel chip, each chip's rows alone (the exchange
between chips left out).  A step that returns its state unchanged reads
1 on ``update_gap`` by the definition and needs no run.

One JSON line per seed goes to standard output and to ``--out``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated non-negative whole numbers")
    ap.add_argument("--faults", type=int, default=3,
                    help="read the control and the faults on this many of "
                         "the seeds")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    import numpy as np

    import chip_harness as h
    import token_generator
    from correctness import compare

    cell = h.load_cell(args.workload)
    h.chips_or_fail(cell.chips, require_chip=True)
    h.use_compile_cache()
    prog = h.build_program(cell)
    t = cell.traffic
    ndp = int(np.prod(t["mesh"][:-1]))
    out = open(args.out, "a") if args.out else None
    for n, seed in enumerate(seeds):
        row = {"workload": cell.name, "seed": seed}
        pool = token_generator.pool_for(t, prog.cfg.vocab, seed)
        key = h.weight_key(seed)
        t0 = time.perf_counter()
        state, prog_rec, _ = h.first_steps(prog, cell, pool, key)
        row["first_steps_s"] = time.perf_counter() - t0
        if n == 0:
            with jax.set_mesh(prog.mesh):
                ma = prog.jstep.lower(*state, {"tokens": pool[0]}) \
                    .compile().memory_analysis()
            row["memory_analysis"] = {
                k: int(getattr(ma, k)) for k in dir(ma)
                if k.endswith("_in_bytes")}
            row["memory_stats"] = [d.memory_stats()
                                   for d in prog.mesh.devices.flat]
            row["setup_to_first_record_s"] = time.perf_counter() - T_START
        del state
        t0 = time.perf_counter()
        ref = h.reference_record(cell, prog, pool, key)
        row["reference_s"] = time.perf_counter() - t0
        row["program_losses"] = prog_rec["losses"]
        row["reference_losses"] = ref["losses"]
        row["sound"] = compare(prog_rec, ref)
        if n < args.faults:
            t0 = time.perf_counter()
            row["control"] = compare(h.reference_record(
                cell, prog, pool, key, precision="fp8"), ref)
            row["control_s"] = time.perf_counter() - t0
            row["half_batch"] = compare(h.reference_record(
                cell, prog, pool, key, rows=t["batch"] // 2), ref)
            if ndp > 1:
                row["no_exchange"] = compare(h.reference_record(
                    cell, prog, pool, key, rows=t["batch"] // ndp), ref)
        for k in ("sound", "control", "half_batch", "no_exchange"):
            if k in row:
                row[k].pop("left_out_of_update", None)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()


if __name__ == "__main__":
    main()
