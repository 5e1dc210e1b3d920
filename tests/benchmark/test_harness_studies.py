"""Each study must do its own work and compile nothing: the per-study
assertions pass on sound studies and fire when a cache is kept or a shape
changes."""

from __future__ import annotations

import pytest
from conftest import tiny_study

from benchmark import harness


@pytest.fixture(scope="module")
def compiles():
    return harness.CompileCounter()


WORKLOADS = ["catalog.study", "catalog.acc"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_studies_pass(tiny_bench, compiles, workload):
    _, study = tiny_study(tiny_bench, workload)
    harness.one_study(study, -1, compiles, annotate=False)  # warm-up: compiles
    for i in range(2):
        record, out, why = harness.one_study(study, i, compiles, annotate=False)
        assert why is None and record.compiled == 0 and out is not None
        assert record.wall_s > 0


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sweep_study_served_from_a_cache_fails(tiny_bench, compiles, workload):
    _, study = tiny_study(tiny_bench, workload)
    kept = study.scenario()
    study.scenario = lambda: kept  # every study runs one scenario object
    harness.one_study(study, -1, compiles, annotate=False)
    _, _, why = harness.one_study(study, 0, compiles, annotate=False)
    assert why and "grid.periods" in why


def test_a_new_shape_inside_the_window_is_counted(tiny_bench, compiles):
    _, study = tiny_study(tiny_bench, "catalog.study")
    harness.one_study(study, -1, compiles, annotate=False)
    study.bids = study.bids[:-1]  # one bid fewer: a grid of a new shape
    record, _, _ = harness.one_study(study, 0, compiles, annotate=False)
    assert record.compiled > 0
