"""BENCHMARK.json's cells find their files by name, and every name, unit
and field keeps to the benchmark's rules."""
import json
import os
import re

import pytest

import harness
import structures

SPEC = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_lines():
    names = []
    for c in SPEC["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m) - {"workloads"} <= {"name", "unit", "better", "bound",
                                          "source", "layer", "moves"}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names += CELLS + [m["name"] for m in SPEC["end_to_end"]
                      + SPEC["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for text in [c["why"] for c in SPEC["configs"] + SPEC["workloads"]] \
            + [m["layer"] for m in SPEC["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = harness.find_cell(cell)
    runner = harness.load_runner(c.traffic["kind"])
    assert all(callable(getattr(runner, f))
               for f in ("run", "finish", "calibrate"))
    assert structures.of(c.config).__name__.startswith("structures.")
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_reader(m["name"]))
    numbers = set(c.limits) - {"control"}
    assert numbers and numbers <= {"loss_gap", "grad_norm_gap",
                                   "change_norm_gap"}
    for k in numbers:
        assert c.limits[k]["limit"] >= 0
    assert set(c.limits["control"]) in ({"policy", "why"},
                                        {"rounding", "why"})
    assert "TPU v5 lite" in c.peaks["devices"]


def test_per_layer_moves_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", CELLS):
            assert harness.reports(moved, w)


def test_check_fits_its_time_with_the_full_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
