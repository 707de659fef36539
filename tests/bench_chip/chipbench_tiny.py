"""A cell of the benchmark cut to a size a CPU test run can hold: the
cell's own files, with every width and the depth cut, driven through
the harness with its look for a chip skipped."""
import dataclasses

from chipbench_paths import BENCH  # noqa: F401

import chip_harness

WIDTHS = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
              vocab_size=512)
# limits for this size, set from CPU readings at it (3 seeds): sound runs
# read loss_gap <= 2.4e-4, grad_gap <= 0.0019 and update_gap <= 0.002;
# the float8 control read >= 8.8e-4, >= 0.015 and >= 0.0062
LIMITS = {"loss_gap": 5e-4, "grad_gap": 0.006, "update_gap": 0.004}


def tiny_cell(name: str) -> chip_harness.Cell:
    cell = chip_harness.load_cell(name)
    config = dict(cell.config, **WIDTHS, reduced=sorted(WIDTHS))
    traffic = dict(cell.traffic, batch=8, seq=64, pool_batches=4,
                   reference=dict(cell.traffic["reference"], rows_per_block=4,
                                  query_block=32, head_chunk=32))
    return dataclasses.replace(cell, config=config, traffic=traffic,
                               limits=LIMITS)


def run(cell, seed=2 ** 31 + 77, seconds=0.5):
    import time
    return chip_harness.run_cell(cell, seed, seconds, False,
                                 t_start=time.perf_counter(),
                                 require_chip=False)
