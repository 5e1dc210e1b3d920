"""The vectorized fleet engine's phase spans and counters, and its biller.

``run_fleet(engine="batch"|"jax")`` names its phases: ``fleet.inputs`` (the
input build, on a cache miss only), ``fleet.place_wave`` per placement wave
with a ``fleet.score`` child per EET scoring call, ``fleet.sim_wave`` per
simulation wave, and ``fleet.replay`` around the per-cell ``fleet.cell``
spans.  The counters ``fleet_batch.*`` and ``fleet_step.*`` count what the
waves did, and none of it changes a result.  ``_bill_flat`` bills each run
as ``core.billing.run_cost`` does, bit for bit.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro import obs
from repro.core import billing
from repro.core.billing import Termination
from repro.core.market import HOUR, TraceModel, catalog
from repro.core.schemes import Scheme
from repro.engine import fleetgrid
from repro.engine.fleetgrid import run_fleet
from repro.fleet import batch as fleet_batch
from repro.kernels.fleet_step import ops

from test_batch_parity import assert_grid_equal, small_scenario

ENGINES = ["batch", "jax"]


def _impl(engine: str) -> str:
    return "jax" if engine == "jax" else "numpy"


def _traced(scenario, engine, monkeypatch=None):
    """One run from empty input caches; returns ``(result, telemetry, waves)``:
    ``waves`` holds the lane count of every scoring call and, per phase-1
    round, its placement lanes."""
    if engine == "jax":
        pytest.importorskip("jax")
    fleetgrid._INPUTS_CACHE.clear()
    waves = {"scored": [], "placed": []}
    if monkeypatch is not None:
        inner_score, inner_place = ops.eet_scores, fleet_batch._BatchFleet._place

        def score(p_fail, *a, **k):
            waves["scored"].append(p_fail.shape)
            return inner_score(p_fail, *a, **k)

        def place(self, reqs):
            waves["placed"].append(len(reqs))
            return inner_place(self, reqs)

        monkeypatch.setattr(ops, "eet_scores", score)
        monkeypatch.setattr(fleet_batch._BatchFleet, "_place", place)
    with obs.Telemetry() as tel:
        res = run_fleet(scenario, engine=engine)
    return res, tel, waves


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("scheme", [Scheme.ACC, Scheme.HOUR])
def test_phase_spans_and_nesting(engine, scheme):
    scenario = small_scenario(scheme=scheme, policies=("algorithm1", "diversified"))
    res, tel, _ = _traced(scenario, engine)
    assert len(tel.find_spans("fleet.inputs")) == 1
    waves = tel.find_spans("fleet.place_wave")
    assert waves and all(c.name == "fleet.score" for w in waves for c in w.children)
    scores = tel.find_spans("fleet.score")
    assert scores and {s.attrs["impl"] for s in scores} == {_impl(engine)}
    assert sum(len(w.children) for w in waves) == len(scores)  # every score inside a wave
    sims = tel.find_spans("fleet.sim_wave")
    assert sims and {s.attrs["scheme"] for s in sims} == {scheme.value}
    (replay,) = tel.find_spans("fleet.replay")
    assert [c.name for c in replay.children] == ["fleet.cell"] * len(res.results)
    assert len(tel.find_spans("fleet.cell")) == len(res.results)
    # the controller's per-job spans stay the controller's
    assert not tel.find_spans("fleet.place") and not tel.find_spans("fleet.migrate")


def test_inputs_span_only_on_a_cache_miss():
    scenario = small_scenario(scheme=Scheme.ACC)
    _traced(scenario, "batch")
    with obs.Telemetry() as tel:
        run_fleet(scenario, engine="batch")
    assert not tel.find_spans("fleet.inputs")


@pytest.mark.parametrize("engine", ENGINES)
def test_wave_counters_count_what_the_waves_did(engine, monkeypatch):
    scenario = small_scenario(scheme=Scheme.ACC, seeds=(0, 1, 2))
    _, tel, waves = _traced(scenario, engine, monkeypatch)
    shapes = waves["scored"]
    assert shapes and tel.counter("fleet_step.calls") == len(shapes)
    assert tel.counter("fleet_step.lanes") == sum(lanes for lanes, _ in shapes)
    assert tel.counter("fleet_batch.placements") == sum(waves["placed"])
    # one round per placement wave: the arrivals, then each round's migrations
    assert tel.counter("fleet_batch.rounds") >= len(waves["placed"])
    if engine == "jax":
        cells = sum(ops._bucket(lanes) * types for lanes, types in shapes)
        assert tel.counter("fleet_step.cells") == cells
        assert tel.counter("fleet_step.h2d_bytes") == cells * (3 * 8 + 1)
        assert tel.counter("fleet_step.d2h_bytes") == cells * 8
    else:
        assert tel.counter("fleet_step.cells") == sum(lanes * types for lanes, types in shapes)
        assert not tel.counter("fleet_step.h2d_bytes") and not tel.counter("fleet_step.d2h_bytes")


@pytest.mark.parametrize("engine", ENGINES)
def test_spans_change_no_result(engine):
    scenario = small_scenario(scheme=Scheme.ACC)
    traced, _, _ = _traced(scenario, engine)
    fleetgrid._INPUTS_CACHE.clear()
    plain = run_fleet(scenario, engine=engine)
    assert_grid_equal(plain, traced)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bill_flat_is_run_cost_bit_for_bit(seed):
    it = catalog()[seed * 7]
    trace = TraceModel.for_instance(it).sample(10 * 24 * HOUR, seed)
    rng = np.random.default_rng(seed)
    launch = rng.uniform(0.0, 8 * 24 * HOUR, 400)
    end = np.minimum(launch + rng.exponential(20 * HOUR, 400), trace.horizon)
    end[:20] = launch[:20]  # zero-length runs bill nothing
    end[20:40] = launch[20:40] + HOUR * rng.integers(1, 30, 20)  # ends on an hour boundary
    user = rng.random(400) < 0.5
    got = fleet_batch._bill_flat(trace, launch, end, user, HOUR)
    want = [billing.run_cost(trace, a, b, Termination.USER if u else Termination.OUT_OF_BID, HOUR)
            for a, b, u in zip(launch, end, user)]
    assert [float(g) for g in got] == want
