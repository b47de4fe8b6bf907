"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files the harness finds by that name."""
import importlib
import json
import re

import pytest

from perfbench.lib import cell
from perfbench.lib.cell import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
E2E = {m["name"] for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + CELLS \
        + [c["name"] for c in BENCH["configs"]] \
        + [w["traffic"] for w in BENCH["workloads"]] \
        + [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(CELLS)) == len(CELLS)


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"]: m["bound"] for m in BENCH["end_to_end"]}[
        "setup_s"] == 0.25


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_its_reader(metric):
    assert callable(importlib.import_module(
        f"perfbench.metrics.{metric['name']}").read)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_each_per_layer_metric_moves_an_end_to_end_metric_its_cells_report(
        metric):
    assert metric["moves"] in E2E
    for name in metric.get("workloads", CELLS):
        reported = {m["name"] for m in cell.load(name).end_to_end}
        assert metric["moves"] in reported, (metric["name"], name)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_and_reports_enough(name):
    c = cell.load(name)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    assert c.chips in (1, 4)
    assert set(c.limits) == {"median_rank"}


def test_configs_state_their_cuts():
    for conf in BENCH["configs"]:
        raw = json.loads((ROOT / conf["file"]).read_text())
        assert conf["file"].startswith("perfbench/configs/")
        published = raw.get("published", {})
        assert sorted(published) == sorted(conf["reduced"]), conf["name"]
        for key in conf["reduced"]:
            assert raw[key] != published[key]
            assert not key.endswith(("_size", "_dim", "_rank", "_heads"))


def test_a_full_check_fits_its_time():
    n = 24      # the most cells a benchmark may hold
    assert (2 + 14 * n) * (BENCH["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
