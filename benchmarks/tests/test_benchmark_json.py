"""BENCHMARK.json against the contract's form, and against the data files
it names."""

import json
import os
import re

import pytest

from conftest import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_name_and_unit_is_within_the_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_cells_report_what_the_contract_asks(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert len({(w["config"], w["traffic"])
                for w in bench["workloads"]}) == len(cells)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if m["name"] != "setup_s"
                and cell in m.get("workloads", cells)]
        assert mine, f"{cell} reports no end-to-end metric but setup_s"
        layers = [m for m in bench["per_layer"]
                  if cell in m.get("workloads", cells)]
        assert layers
        for m in layers:      # a layer metric moves a metric its cell has
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_entries_agree_with_the_files_they_name(bench):
    for c in bench["configs"]:
        f = _load("configs", f"{c['name']}.json")
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert (f["name"], f["source"], f["reduced"]) == (
            c["name"], c["source"], c["reduced"])
    for w in bench["workloads"]:
        f = _load("workloads", f"{w['name']}.json")
        assert {k: f[k] for k in ("name", "config", "traffic", "chips",
                                  "why")} == w
        _load("traffic", f"{w['traffic']}.json")
        assert os.path.exists(os.path.join(BENCH_DIR, "drivers",
                                           f"{f['driver']}.py"))
    for m in bench["per_layer"]:
        f = _load("layer_metrics", f"{m['name']}.json")
        assert (f["name"], f["unit"], f["layer"], f["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                           f"{f['reader']}.py"))


def test_no_file_of_a_cell_that_is_not_listed(bench):
    """Every data file belongs to an entry: nothing frozen under `paths`
    that no cell, configuration or metric of BENCHMARK.json names."""
    for folder, names in (
            ("configs", {c["name"] for c in bench["configs"]}),
            ("workloads", {w["name"] for w in bench["workloads"]}),
            ("traffic", {w["traffic"] for w in bench["workloads"]}),
            ("layer_metrics", {m["name"] for m in bench["per_layer"]})):
        found = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, folder))
                 if f.endswith(".json")}
        assert found == names, folder


def test_peaks_table_names_its_source():
    for kind, row in _load("peaks.json").items():
        assert row["source"] and row["bf16_flops_per_s"] > 0
        assert row["hbm_bytes_per_s"] > 0
