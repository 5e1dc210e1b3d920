"""Fixtures for the benchmark's own tests: the checkout on ``sys.path`` and
tiny copies of each configuration, so a whole run fits a CPU test."""

from __future__ import annotations

import copy
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: each configuration cut to a few cells; every other key as committed
TINY = {
    "paper_catalog": {"instances": ["m1.small.us-east-1.linux", "c1.xlarge.eu-west-1.linux",
                                    "m2.xlarge.us-west-1.windows"],
                      "bid_fractions": [0.5, 0.53, 0.56, 0.6], "ensemble_seeds": [0, 1]},
}


@pytest.fixture
def tiny_bench(tmp_path):
    """``BENCHMARK.json`` with every configuration file swapped for a tiny copy."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = copy.deepcopy(bench)
    for c in bench["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        config.update(TINY[c["name"]])
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(config))
        c["file"] = str(path)
    return bench


def tiny_study(bench, workload: str, seed: int = 2**31 + 7):
    """A study of ``workload`` at its tiny size, with its kind module."""
    import importlib

    from benchmark import harness

    _, config, traffic, _, _ = harness.cell_inputs(bench, workload)
    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    return kind, kind.Study(config, traffic, seed)
