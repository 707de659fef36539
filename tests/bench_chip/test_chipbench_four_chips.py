"""The four-chip cell's path at a size the CPU holds, on four virtual
devices: a sound run is correct, and a run whose EDST sync leaves out
the exchange between chips (each chip keeps its own gradient, at the
right scale) is not."""
import json

from chipbench_paths import BENCH, REPO

CODE = """
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import chip_harness
chip_harness.use_compile_cache = lambda: None
from chipbench_tiny import run, tiny_cell
cell = tiny_cell("smollm-135m.train.edst.4chip")
sound, _ = run(cell)
import repro.dist.steps as steps
steps.tree_allreduce = lambda x, spec, quantize=False, segments="auto": x * 4
broken, numbers = run(cell)
print(json.dumps({{"sound": sound["correct"], "broken": broken["correct"],
                  "count": sound["device"]["count"],
                  "grad_gap": numbers["grad_gap"]}}))
"""


def test_four_devices_sound_and_no_exchange(subproc):
    code = CODE.format(bench=str(BENCH), tests=str(BENCH.parents[1] / "tests"
                                                   / "bench_chip"),
                       src=str(REPO / "src"))
    out = json.loads(subproc(code, 4).strip().splitlines()[-1])
    assert out == {"sound": True, "broken": False, "count": 4,
                   "grad_gap": out["grad_gap"]}
    assert out["grad_gap"] > 0.1
