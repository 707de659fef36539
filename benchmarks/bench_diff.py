"""CI gate for the allreduce perf trajectory: diff a fresh bench JSON
against a committed baseline and fail on regressions of any gated
``exec/*`` row.

Absolute microseconds are not comparable across machines, so every
``exec/<fabric>/<engine>`` row is normalized by its fabric's
``exec/<fabric>/psum`` row from the SAME file before comparing: psum is
the XLA-native collective both runs execute on identical hardware, which
cancels host speed and iteration-count differences and leaves the
engine-vs-XLA ratio the trajectory actually tracks.  Payload size does
NOT cancel (smaller payloads shift every tree engine toward the
alpha-dominated regime), so rows are only compared when baseline and new
agree on ``bytes`` -- CI therefore diffs its ``--quick`` run against the
committed ``BENCH_allreduce_quick.json``, not the full-run trajectory
file.  The ``pipelined_s{2,4,8}`` sweep rows are informational (the S>1
scan serializes its per-step waves on host backends by design, ~10x the
headline rows and noisy at smoke iteration counts) and are excluded from
the gate.  Every other ``exec/*`` engine row IS gated -- including the
``striped`` / ``striped_q8`` reduce-scatter/allgather rows (slower than
pipelined on alpha-dominated hosts by design, but their *ratio to psum*
must not drift) -- and ``calibration/*`` / ``compile/*`` rows are not
exec rows, so they never gate.  A gated row regresses when its
normalized cost grows by more than ``--threshold`` (default 1.25x).

    python -m benchmarks.bench_diff --baseline BENCH_allreduce_quick.json \
        --new /tmp/new.json --threshold 1.25
"""
from __future__ import annotations

import argparse
import json
import sys


def normalized_exec(results: dict) -> dict:
    """exec/<fabric>/<engine> -> (us_per_call / same-fabric psum us, bytes)."""
    out = {}
    for name, row in results.items():
        if not name.startswith("exec/"):
            continue
        fabric = name.split("/")[1]
        psum = results.get(f"exec/{fabric}/psum")
        if psum is None or psum["us_per_call"] <= 0:
            continue
        out[name] = (row["us_per_call"] / psum["us_per_call"],
                     row.get("bytes"))
    return out


def diff(baseline: dict, new: dict, threshold: float):
    """(rows, regressions): rows are (name, base_norm, new_norm, ratio)."""
    base_n, new_n = normalized_exec(baseline), normalized_exec(new)
    rows, regressions = [], []
    for name in sorted(base_n):
        if name.endswith("/psum") or name not in new_n:
            continue
        if "/pipelined_s" in name:   # informational sweep, not gated
            continue
        (b, b_bytes), (n, n_bytes) = base_n[name], new_n[name]
        if b_bytes != n_bytes:       # cross-payload ratios don't compare
            continue
        ratio = n / b
        rows.append((name, b, n, ratio))
        if ratio > threshold:
            regressions.append(name)
    return rows, regressions


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline")
    ap.add_argument("--new")
    ap.add_argument("--threshold", type=float, default=1.25)
    args = ap.parse_args()

    if not args.baseline or not args.new:
        ap.error("--baseline and --new are required")
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.new) as f:
        new = json.load(f)

    rows, regressions = diff(baseline, new, args.threshold)
    if not rows:
        print("bench_diff: no comparable exec/* rows (payload size or "
              "fabric set changed without regenerating the baseline, or "
              "psum rows missing) -- an empty comparison disables the "
              "gate, so this is an error; regenerate the baseline file")
        return 1
    width = max(len(name) for name, *_ in rows)
    print(f"{'row':<{width}}  {'base(xpsum)':>12} {'new(xpsum)':>12} "
          f"{'ratio':>7}")
    for name, b, n, r in rows:
        mark = "  <-- REGRESSION" if name in regressions else ""
        print(f"{name:<{width}}  {b:>12.2f} {n:>12.2f} {r:>7.2f}{mark}")
    if regressions:
        print(f"\n{len(regressions)} row(s) regressed beyond "
              f"{args.threshold:.2f}x vs baseline")
        return 1
    print(f"\nall rows within {args.threshold:.2f}x of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
