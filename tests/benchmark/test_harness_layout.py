"""Every configuration, traffic mix and per-layer metric is a file of its own,
found by the name ``BENCHMARK.json`` gives it, and the file keeps to the
benchmark's contract."""

from __future__ import annotations

import json
import re

import pytest
from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTHS = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|projection|head")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_found_by_name(config):
    assert NAME.match(config["name"])
    assert config["file"] == f"benchmark/configs/{config['name']}.json"
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["reduced"] == config["reduced"]
    assert not [k for k in config["reduced"] if WIDTHS.search(k)]
    assert data["source"] == config["source"]
    assert data["assumed"] and data["precision"] == "float64"
    assert (ROOT / "benchmark" / "kinds" / f"{data['kind']}.py").is_file()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_names_its_files(cell):
    from benchmark import harness

    assert NAME.match(cell["name"]) and cell["chips"] == 1 and len(cell["why"]) <= 200
    _, config, traffic, e2e, per_layer = harness.cell_inputs(BENCH, cell["name"])
    assert traffic["kind"] == config["kind"]
    assert {m["name"] for m in e2e} == {"study_s", "setup_s"}
    assert per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    from benchmark import harness

    assert metric["moves"] == "study_s"
    assert set(metric["workloads"]) <= {c["name"] for c in BENCH["workloads"]}
    reader = harness.load_reader(metric["name"])
    empty = harness.RunData(studies=[], trace=None, shapes={}, peaks={})
    assert reader.read(empty) is None  # nothing to read gives no number, never 0


def test_layers_are_named_alike():
    by_layer: dict[str, set[str]] = {}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] == "host_clock" and 0.01 <= m["bound"] <= 0.25
