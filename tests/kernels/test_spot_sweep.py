"""spot_sweep op: the fused lockstep sweep program vs the NumPy driver.

The reference (:func:`repro.engine.batch.run_schemes_numpy`) is the
production BatchEngine driver, itself proven ``==`` against the scalar event
loop — so the one-compile ``lax.scan`` program is held to **exact** equality
on every output field, ADAPT's dynamic binned-hazard decisions included.
Skipped (not failed) when jax is absent, like every other kernel suite.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import Scheme, SimParams, get_instance, step_trace, synthetic_trace
from repro.engine import BID_LIMITED_SCHEMES, Scenario
from repro.engine.batch import grid_and_tables, run_schemes_numpy
from repro.kernels.spot_sweep import ops as sweep_ops

IT = get_instance("m1.xlarge")

FIELDS = ("completed", "completion_time", "cost", "n_checkpoints", "n_kills", "work_lost_s")


def small_scenario(**kw):
    """One synthetic trace, short horizon, a handful of cells."""
    tr = synthetic_trace(IT, kw.pop("days", 5), seed=kw.pop("seed", 3))
    return Scenario.from_trace(
        tr,
        kw.pop("work_h", 6.0) * 3600.0,
        bids=kw.pop("bids", [0.34, 0.355, 0.36, 0.37]),
        schemes=kw.pop("schemes", BID_LIMITED_SCHEMES),
        **kw,
    )


def _grid(sc):
    return grid_and_tables(sc, sc.materialize(), Scheme.ADAPT in sc.schemes)


def run_scan(sc):
    """``(outs, info)`` of the device sweep program on ``sc``."""
    grid, tables = _grid(sc)
    return sweep_ops.spot_sweep_grid(sc.schemes, grid, sc, tables)


def run_ref(sc):
    """The NumPy driver's per-scheme outputs on ``sc``."""
    grid, tables = _grid(sc)
    outs, _ = run_schemes_numpy(sc.schemes, grid, sc, tables)
    return outs


def assert_outs_equal(ref, cand):
    for scheme, out in ref.items():
        for field in FIELDS:
            np.testing.assert_array_equal(
                out[field], cand[scheme][field], err_msg=f"{scheme.value}.{field}"
            )


def test_sweep_impls_match_ref_exactly():
    sc = small_scenario()
    cand, info = run_scan(sc)
    assert info["impl"] == "scan"
    assert_outs_equal(run_ref(sc), cand)


def test_scan_handles_resume_and_extreme_bids():
    """Never-available, always-available and mid-job-resume cells through the
    fused program."""
    tr = synthetic_trace(IT, 20, seed=7)
    sc = Scenario.from_trace(
        tr,
        30 * 3600.0,
        bids=[0.01, 0.30, 0.345, 0.36, 5.0],
        schemes=BID_LIMITED_SCHEMES,
        initial_saved_work=10 * 3600.0,
        params=SimParams(t_c=450.0, t_r=900.0),
    )
    cand, _ = run_scan(sc)
    assert_outs_equal(run_ref(sc), cand)


def test_scan_scheme_subsets_match_full_program():
    """Each scheme evaluated alone equals its slice of the fused 5-scheme
    program (the segment axis cannot couple schemes)."""
    sc = small_scenario()
    full, _ = run_scan(sc)
    for scheme in sc.schemes:
        sub = Scenario.from_trace(
            sc.traces[0], sc.work_s, sc.bids, schemes=(scheme,), params=sc.params
        )
        solo, _ = run_scan(sub)
        for field in FIELDS:
            np.testing.assert_array_equal(
                solo[scheme][field], full[scheme][field], err_msg=f"{scheme.value}.{field}"
            )


def test_step_trace_edge_cases():
    """Hand-built step trace with degenerate periods through the scan
    program — exercises shorts, censored tails and EDGE cursors."""
    day = 24 * 3600.0
    tr = step_trace(
        [(0.0, 0.30), (0.4 * day, 0.50), (0.45 * day, 0.31), (1.3 * day, 0.52),
         (1.35 * day, 0.29), (2.0 * day, 0.55)],
        horizon_s=3 * day,
    )
    sc = Scenario.from_trace(
        tr, 10 * 3600.0, bids=[0.295, 0.32, 0.51], schemes=BID_LIMITED_SCHEMES
    )
    cand, _ = run_scan(sc)
    assert_outs_equal(run_ref(sc), cand)
