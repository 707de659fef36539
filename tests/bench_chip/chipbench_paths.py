"""Puts the benchmark's directory and the program's sources on the path
for the benchmark's own tests."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
DATA = Path(__file__).resolve().parent / "data"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
