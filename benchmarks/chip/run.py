#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up builds the program's train step, makes the cell's weights and a
pool of token batches from ``--seed``, and drives the first steps; the
window then drives the same step for ``--seconds``.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.  After the window the
plain reference follows the first steps, and ``correct`` says whether
every compared number is within its limit.

The last line of standard output is the result as one JSON object; the
last lines of standard error give each compared number beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import chip_harness
    cell = chip_harness.load_cell(args.workload)
    try:
        result, numbers = chip_harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except chip_harness.NoChip as e:
        sys.exit(f"run.py: {e}; nothing was run")
    print(f"worst grad leaf {numbers['worst_grad_leaf']}, worst update "
          f"leaf {numbers['worst_update_leaf']}, left out of the update "
          f"gap: {numbers['left_out_of_update']}, loss gap of each step "
          f"{numbers['step_loss_gaps']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
