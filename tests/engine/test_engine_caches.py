"""Compiled-program reuse and cross-backend cache sharing.

The perf contract behind `jax >= batch`: the fused program compiles once per
scheme set and grid shape (re-running never retraces), and the derived
simulation inputs — period grid, ADAPT decision tables, binned survival
tables — are built once per scenario and shared by every backend in the
process.
"""

import numpy as np
import pytest

from repro.core import Scheme, catalog, get_instance, synthetic_trace
from repro.core.schemes import FailurePdf
from repro.engine import BID_LIMITED_SCHEMES, Scenario, get_engine, run
from repro.engine import batch as batch_mod
from repro.engine.kernels import AdaptTables

IT = get_instance("m1.xlarge")


def _grid_scenario():
    types = [it for it in catalog() if it.os == "linux"][:2]
    return Scenario.grid(
        work_s=12 * 3600.0,
        bids=[round(0.50 + 0.02 * i, 3) for i in range(3)],
        instances=types,
        schemes=BID_LIMITED_SCHEMES,
        horizon_days=10.0,
        seeds=(0, 1),
        bid_fractions=True,
    )


def test_compact_survival_is_cached_per_pdf():
    """One table object per pdf: scalar ADAPT, provisioning and the engine
    decision tables all read the same floats (and the same memory)."""
    tr = synthetic_trace(IT, 10, seed=0)
    pdf = FailurePdf.from_trace(tr, 0.36)
    assert pdf.survival_table() is pdf.survival_table()
    v1, top1 = pdf.compact_survival()
    v2, top2 = pdf.compact_survival()
    assert v1 is v2 and top1 == top2


def test_grid_and_tables_built_once_per_scenario(monkeypatch):
    """Two batch runs of one scenario: one _PeriodGrid build, one AdaptTables
    build (the WeakKeyDictionary scenario cache)."""
    calls = {"grid": 0, "tables": 0}
    orig_grid, orig_tab = batch_mod._PeriodGrid.build, AdaptTables.build
    monkeypatch.setattr(
        batch_mod._PeriodGrid,
        "build",
        staticmethod(lambda *a, **k: (calls.__setitem__("grid", calls["grid"] + 1), orig_grid(*a, **k))[1]),
    )
    monkeypatch.setattr(
        AdaptTables,
        "build",
        staticmethod(lambda *a, **k: (calls.__setitem__("tables", calls["tables"] + 1), orig_tab(*a, **k))[1]),
    )
    sc = _grid_scenario()
    r1 = run(sc, engine="batch")
    r2 = run(sc, engine="batch")
    assert calls == {"grid": 1, "tables": 1}
    np.testing.assert_array_equal(r1.cost, r2.cost)

    # the cache is keyed on the scenario *object*: an equal but distinct
    # scenario builds its own grid (materialization must stay hermetic)
    run(_grid_scenario(), engine="batch")
    assert calls["grid"] == 2


def test_caches_shared_across_backends(monkeypatch):
    """batch then jax then pallas on one scenario object: the grid and the
    ADAPT tables are built exactly once, and all backends agree exactly."""
    pytest.importorskip("jax")
    calls = {"grid": 0, "tables": 0}
    orig_grid, orig_tab = batch_mod._PeriodGrid.build, AdaptTables.build
    monkeypatch.setattr(
        batch_mod._PeriodGrid,
        "build",
        staticmethod(lambda *a, **k: (calls.__setitem__("grid", calls["grid"] + 1), orig_grid(*a, **k))[1]),
    )
    monkeypatch.setattr(
        AdaptTables,
        "build",
        staticmethod(lambda *a, **k: (calls.__setitem__("tables", calls["tables"] + 1), orig_tab(*a, **k))[1]),
    )
    sc = Scenario.from_trace(
        synthetic_trace(IT, 6, seed=2),
        8 * 3600.0,
        bids=[0.34, 0.36, 0.37],
        schemes=BID_LIMITED_SCHEMES,
    )
    from repro.engine import PallasEngine

    engines = {"batch": "batch", "jax": "jax", "pallas": PallasEngine(interpret=True)}
    results = {name: run(sc, engine=eng) for name, eng in engines.items()}
    assert calls == {"grid": 1, "tables": 1}
    for name in ("jax", "pallas"):
        np.testing.assert_array_equal(results["batch"].cost, results[name].cost)
        np.testing.assert_array_equal(
            results["batch"].completion_time, results[name].completion_time
        )


def test_jax_engine_does_not_retrace_same_grid_shape():
    """The one-compile contract: re-running a scenario — or a re-created
    equal scenario (same grid shape, fresh trace objects) — reuses the
    compiled multi-scheme program without retracing."""
    pytest.importorskip("jax")
    from repro.kernels.spot_sweep import ops as sweep_ops

    eng = get_engine("jax")
    sc = _grid_scenario()
    eng.run(sc)
    traced = sweep_ops.trace_count(BID_LIMITED_SCHEMES)
    assert traced >= 1  # compiled at least once somewhere in this process

    eng.run(sc)  # same scenario object: cached grid, cached program
    assert sweep_ops.trace_count(BID_LIMITED_SCHEMES) == traced

    eng.run(_grid_scenario())  # fresh equal scenario: same shapes, no retrace
    assert sweep_ops.trace_count(BID_LIMITED_SCHEMES) == traced

    # a second engine instance shares the module-level program cache too
    get_engine("jax").run(_grid_scenario())
    assert sweep_ops.trace_count(BID_LIMITED_SCHEMES) == traced


def test_scenario_cache_returns_identical_objects():
    sc = _grid_scenario()
    g1, t1 = batch_mod.grid_and_tables(sc, sc.materialize(), True)
    g2, t2 = batch_mod.grid_and_tables(sc, sc.materialize(), True)
    assert g1 is g2 and t1 is t2
    assert isinstance(t1, AdaptTables)
