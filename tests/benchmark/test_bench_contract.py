"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by that name."""
import json
import os
import re

import pytest

from bench_util import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    # a full check with all 24 cells has to fit the driver's day
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text_fields(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200


def test_end_to_end_metrics_have_bounds(bench):
    names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in names
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace")


def test_every_per_layer_metric_lists_cells_that_report_what_it_moves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
        assert m["moves"] in e2e
        assert m["workloads"] and set(m["workloads"]) <= cells
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved_in, m["name"]
    names = {m["name"] for m in bench["per_layer"]}
    assert any("mfu" in n.split(".") or n.startswith("mfu") for n in names)
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])


def test_every_name_has_its_file(bench):
    base = os.path.join(ROOT, "benchmark")
    files = set()
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert os.path.isfile(os.path.join(
            base, "families", data["family"] + ".py"))
        assert os.path.isfile(os.path.join(
            base, "reference", data["family"] + ".py"))
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(os.path.join(base, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(
            base, "drivers", traffic["driver"] + ".py"))
        assert os.path.isfile(os.path.join(
            base, "limits", w["name"] + ".json"))
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            base, "metrics", m["name"] + ".py")), m["name"]


def test_no_width_is_reduced(bench):
    for c in bench["configs"]:
        for key in c["reduced"]:
            assert not re.search(
                r"(_dim|_rank|embd|hidden|inner|head|intermediate)", key), key
