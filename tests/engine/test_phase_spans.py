"""The phases the engine names inside a study: trace generation
(``materialize``), the device sweep's host↔device round trip (``sim.*`` and
the ``sweep.*_bytes`` counters) and the biller's work (``bill.runs``,
``bill.hours``)."""

import numpy as np
import pytest

from repro import obs
from repro.core import get_instance
from repro.engine import BID_LIMITED_SCHEMES, Scenario, run

SWEEP_PHASES = ["sim.inputs", "sim.h2d", "sim.device", "sim.fetch"]


def _scenario(schemes=BID_LIMITED_SCHEMES):
    its = [get_instance("m1.xlarge"), get_instance("c1.medium")]
    return Scenario.grid(work_s=6 * 3600.0, bids=(0.5, 0.55, 0.6), instances=its,
                         schemes=schemes, horizon_days=8, seeds=[0, 1], bid_fractions=True)


def _scan_span(tel):
    (sim,) = [s for s in tel.find_spans("sim") if s.attrs.get("impl") == "scan"]
    return sim


@pytest.mark.parametrize("engine", ["batch", "jax"])
def test_materialize_is_a_sibling_of_engine_run(engine):
    if engine == "jax":
        pytest.importorskip("jax")
    with obs.Telemetry() as tel:
        run(_scenario(), engine=engine)
    assert [s.name for s in tel.spans] == ["materialize", "engine.run"]
    (root,) = tel.find_spans("engine.run")
    assert not list(root.find("materialize"))


def test_materialize_span_covers_explicit_traces():
    from repro.core import synthetic_trace

    tr = synthetic_trace(get_instance("m1.xlarge"), 5, seed=3)
    sc = Scenario.from_trace(tr, 3600.0, [0.36])
    with obs.Telemetry() as tel:
        cells = sc.materialize()
    (span,) = tel.spans
    assert span.name == "materialize" and span.dur > 0 and len(cells) == 1


def test_scan_children_in_order_and_only_on_a_cache_miss():
    pytest.importorskip("jax")
    sc = _scenario()
    with obs.Telemetry() as tel:
        run(sc, engine="jax")
    assert [c.name for c in _scan_span(tel).children] == SWEEP_PHASES
    # the same scenario again: its grid and device copies are cached
    with obs.Telemetry() as again:
        run(sc, engine="jax")
    assert [c.name for c in _scan_span(again).children] == ["sim.device", "sim.fetch"]
    assert again.counter("sweep.h2d_bytes") == 0
    assert again.counter("sweep.d2h_bytes") == tel.counter("sweep.d2h_bytes")


def test_acc_sim_span_has_no_sweep_children():
    pytest.importorskip("jax")
    from repro.core import Scheme

    with obs.Telemetry() as tel:
        run(_scenario(schemes=(Scheme.HOUR, Scheme.ACC)), engine="jax")
    (acc,) = [s for s in tel.find_spans("sim") if s.attrs.get("scheme") == "acc"]
    assert not [c for c in acc.children if c.name.startswith("sim.")]


def test_byte_counters_are_the_arrays_moved():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import Scheme
    from repro.engine.batch import grid_and_tables
    from repro.kernels.spot_sweep import ops

    sc = _scenario()
    with obs.Telemetry() as tel:
        run(sc, engine="jax")
    grid, tables = grid_and_tables(sc, sc.materialize(), True)
    host = ops.scan_arrays(grid, True, True, sc.params.t_r, tables)
    assert tel.counter("sweep.h2d_bytes") == sum(np.asarray(v).nbytes for v in host.values())

    schemes = tuple(s for s in sc.schemes if s is not Scheme.ACC)
    kwargs = ops.scan_scalars(sc, True, tables)
    kwargs.update({k: jnp.asarray(v) for k, v in host.items()})
    pairs = jax.block_until_ready(ops._scan_fn(schemes, jax)(**kwargs))
    # per scheme: the five final fields the engine reads, and one uint8 run
    # record code per (period, cell)
    finals = sum(state[j].nbytes for state, _ in pairs for j in (1, 2, 3, 4, 6))
    assert all(code.dtype == np.uint8 and code.shape == grid.B.T.shape for _, code in pairs)
    records = len(schemes) * grid.B.size
    assert tel.counter("sweep.d2h_bytes") == finals + records > 0


def test_bill_counts_are_the_records_billed():
    from repro.engine.batch import _bill_runs_flat, grid_and_tables

    sc = _scenario()
    grid, _ = grid_and_tables(sc, sc.materialize(), False)
    rng = np.random.default_rng(7)
    C, P = grid.A.shape
    cells = rng.integers(0, C, 40)
    periods = rng.integers(0, P, 40)
    launch = np.where(grid.valid[cells, periods], grid.A[cells, periods], 0.0)
    end = launch + rng.uniform(0.0, 5 * 3600.0, 40)
    user = rng.random(40) < 0.5
    delta = sc.params.billing_period_s
    with obs.Telemetry() as tel:
        _bill_runs_flat(grid, periods, cells, launch, end, user, delta)
    hours = sum(int(np.ceil((e - a) / delta - 1e-12)) for a, e in zip(launch, end))
    assert tel.counter("bill.runs") == 40
    assert tel.counter("bill.hours") == hours > 0


def test_bill_counts_agree_across_engines():
    pytest.importorskip("jax")
    with obs.Telemetry() as ref:
        res = run(_scenario(), engine="batch")
    with obs.Telemetry() as tel:
        run(_scenario(), engine="jax")
    assert tel.counter("bill.runs") == ref.counter("bill.runs") >= res.n_kills.sum()
    assert tel.counter("bill.hours") == ref.counter("bill.hours") > 0
