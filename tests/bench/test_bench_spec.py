"""BENCHMARK.json and the files the harness finds by name."""

import json
import re

import pytest

from bench import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells must fit its 43,200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_bounds():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_is_found_by_name(cell):
    entry = harness.find(BENCH["workloads"], cell, "workload")
    cfg = harness.config(entry["config"])
    assert cfg["name"] == entry["config"]
    traffic = harness.traffic(entry["traffic"])
    assert harness.runner_class(traffic["runner"]).__name__ == "Runner"
    e2e, layer = harness.cell_metrics(BENCH, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2 and layer
    assert entry["chips"] == cfg["chips"] == 1


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_metric_has_a_reader(metric):
    read = harness.metric_reader(metric)
    assert read(harness.Record()) is None or metric.startswith("lp_compiles")


def test_config_files_match_their_entries():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])


def test_unknown_names_and_devices_are_errors():
    with pytest.raises(KeyError):
        harness.find(BENCH["workloads"], "no.such.cell", "workload")
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric")
