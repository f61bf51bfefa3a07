"""BENCHMARK.json against the contract's mechanical rules and against the
files it names: what a typo would break is caught here, not on the chip."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit into 43,200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_whys(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("chipbench/")
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["config"] in [c["name"] for c in bench["configs"]]
        names.append(w["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert {c["name"] for c in bench["configs"]} == \
        {w["config"] for w in bench["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_each_per_layer_metric_has_its_file_and_moves_a_metric_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        for k in ("name", "unit", "layer", "moves", "workloads", "source", "better"):
            assert spec[k] == m[k], (m["name"], k)
        mod, fn = spec["reader"].split(":")
        assert os.path.exists(os.path.join(ROOT, "chipbench", "readers", mod + ".py"))
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]], (m["name"], cell)
    # a kernel's roofline stands beside the whole step's share of the peak
    for m in bench["per_layer"]:
        if m["name"].split(".")[1].endswith("_roofline"):
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in bench["per_layer"]), m["name"]


def test_each_cell_finds_its_traffic_or_job_and_its_rehearsal(bench):
    for w in bench["workloads"]:
        found = [k for k in ("traffic", "jobs") if os.path.exists(
            os.path.join(ROOT, "chipbench", k, w["traffic"] + ".json"))]
        assert len(found) == 1, w["name"]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "rehearsal",
                                           w["traffic"] + ".json"))
