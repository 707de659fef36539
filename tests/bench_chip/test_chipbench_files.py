"""The harness finds every file that BENCHMARK.json names, by name, and
BENCHMARK.json keeps to the shape the benchmark's check reads."""
import dataclasses
import json
import re

import pytest

from chipbench_paths import BENCH, REPO

import chip_harness

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    for p in SPEC["paths"]:
        assert (REPO / p).is_dir()
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert layers <= {"train loop (host)", "device", "model step",
                      "EDST sync", "kernels"}
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_file(conf):
    data = chip_harness.load_json(REPO / conf["file"])
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert set(data["published"]) == set(data["reduced"])
    for key in data["reduced"]:
        assert data[key] != data["published"][key]
    # each changed key is either a cut of scale or a departure of the
    # program from the published model, with its reason
    assert set(data["why_reduced"]) | set(data["program_departures"]) \
        == set(data["reduced"])
    assert not set(data["why_reduced"]) & set(data["program_departures"])
    cfg = chip_harness.program_config(data)
    assert cfg.vocab == data["vocab_size"]
    assert cfg.n_layers == data["num_hidden_layers"]
    # every mapped key names a field of the program, and every cut of
    # scale reaches the program
    fields = {**chip_harness.FIELDS, **data.get("program_fields", {})}
    assert set(fields.values()) <= {f.name for f in dataclasses.fields(cfg)}
    for key in data["why_reduced"]:
        assert chip_harness.field_value(cfg, fields[key]) == data[key]


@pytest.mark.parametrize("name", CELLS)
def test_every_file_of_a_cell_is_found(name):
    cell = chip_harness.load_cell(name)
    import correctness
    compared = set(cell.limits) & set(correctness.NUMBERS)
    assert {"grad_gap", "update_gap"} <= compared
    assert set(cell.limits) <= compared | {"readings"}
    assert cell.limits["readings"]
    assert set(cell.end_to_end) == {m["name"] for m in SPEC["end_to_end"]}
    want = {m["name"] for m in SPEC["per_layer"]
            if name in m.get("workloads", [name])}
    assert set(cell.metrics) == want
    for _, mod in cell.metrics.values():
        assert callable(mod.read)
    assert callable(cell.flops.flops_per_token)
    assert callable(cell.reference.train)
    assert callable(cell.kernels["tree_combine"].is_call)
    t = cell.traffic
    assert t["batch"] % (t["mesh"][0] * t["mesh"][1]) == 0
    assert t["mesh"][0] * t["mesh"][1] == next(
        w["chips"] for w in SPEC["workloads"] if w["name"] == name)


def test_peaks_table():
    table = chip_harness.load_json(BENCH / "peaks.json")
    assert table["source"]
    row = chip_harness.peaks_for(table, "TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        chip_harness.peaks_for(table, "TPU v9 imaginary")


def test_a_changed_program_size_is_refused():
    data = chip_harness.load_json(BENCH / "configs" / "smollm-135m.json")
    data["intermediate_size"] = 2048
    with pytest.raises(ValueError):
        chip_harness.program_config(data)
