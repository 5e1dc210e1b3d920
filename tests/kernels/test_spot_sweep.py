"""spot_sweep triad: the fused Pallas lockstep sweep vs the NumPy driver.

The reference (``impl="ref"``) is the production BatchEngine driver, itself
proven ``==`` against the scalar event loop — so both device impls (the
one-compile ``lax.scan`` program and the Pallas kernel in interpreter mode)
are held to **exact** equality on every output field, ADAPT's dynamic
binned-hazard decisions included.  Skipped (not failed) when jax is absent,
like every other kernel suite.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import Scheme, SimParams, get_instance, step_trace, synthetic_trace
from repro.engine import BID_LIMITED_SCHEMES, PallasEngine, Scenario, assert_parity, get_engine
from repro.engine.batch import grid_and_tables
from repro.kernels.spot_sweep import ops as sweep_ops

IT = get_instance("m1.xlarge")

FIELDS = ("completed", "completion_time", "cost", "n_checkpoints", "n_kills", "work_lost_s")


def small_scenario(**kw):
    """One synthetic trace, short horizon — sized so the Pallas interpreter
    (which executes the kernel body once per (cell block, period) grid step)
    stays in test time."""
    tr = synthetic_trace(IT, kw.pop("days", 5), seed=kw.pop("seed", 3))
    return Scenario.from_trace(
        tr,
        kw.pop("work_h", 6.0) * 3600.0,
        bids=kw.pop("bids", [0.34, 0.355, 0.36, 0.37]),
        schemes=kw.pop("schemes", BID_LIMITED_SCHEMES),
        **kw,
    )


def run_impls(sc, impl, **op_kw):
    markets = sc.materialize()
    grid, tables = grid_and_tables(sc, markets, Scheme.ADAPT in sc.schemes)
    outs, timings = sweep_ops.spot_sweep_grid(
        sc.schemes, grid, sc, tables, impl=impl, **op_kw
    )
    return outs, timings


def assert_outs_equal(ref, cand):
    for scheme, out in ref.items():
        for field in FIELDS:
            np.testing.assert_array_equal(
                out[field], cand[scheme][field], err_msg=f"{scheme.value}.{field}"
            )


@pytest.mark.parametrize("impl", ["scan", "interpret"])
def test_sweep_impls_match_ref_exactly(impl):
    sc = small_scenario()
    ref, _ = run_impls(sc, "ref")
    cand, timings = run_impls(sc, impl)
    assert timings["impl"] == impl
    assert_outs_equal(ref, cand)


def test_pallas_block_padding_is_inert():
    """block_c smaller than (and not dividing) the cell count: the padded
    never-available lanes must not change any real cell's bits."""
    sc = small_scenario(bids=[0.33, 0.35, 0.355, 0.36, 0.38])  # C = 5 cells
    ref, _ = run_impls(sc, "ref")
    cand, _ = run_impls(sc, "interpret", block_c=2)
    assert_outs_equal(ref, cand)


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
    ref, _ = run_impls(sc, "ref")
    cand, _ = run_impls(sc, "scan")
    assert_outs_equal(ref, cand)


def test_scan_scheme_subsets_match_full_program():
    """Each scheme evaluated alone equals its slice of the fused 5-scheme
    program (the segment axis cannot couple schemes)."""
    sc = small_scenario()
    full, _ = run_impls(sc, "scan")
    for scheme in sc.schemes:
        sub = Scenario.from_trace(
            sc.traces[0], sc.work_s, sc.bids, schemes=(scheme,), params=sc.params
        )
        solo, _ = run_impls(sub, "scan")
        for field in FIELDS:
            np.testing.assert_array_equal(
                solo[scheme][field], full[scheme][field], err_msg=f"{scheme.value}.{field}"
            )


def test_step_trace_edge_cases_interpret():
    """Hand-built step trace with degenerate periods through the Pallas
    interpreter — exercises shorts, censored tails and EDGE cursors."""
    day = 24 * 3600.0
    tr = step_trace(
        [(0.0, 0.30), (0.4 * day, 0.50), (0.45 * day, 0.31), (1.3 * day, 0.52),
         (1.35 * day, 0.29), (2.0 * day, 0.55)],
        horizon_s=3 * day,
    )
    sc = Scenario.from_trace(
        tr, 10 * 3600.0, bids=[0.295, 0.32, 0.51], schemes=BID_LIMITED_SCHEMES
    )
    ref, _ = run_impls(sc, "ref")
    cand, _ = run_impls(sc, "interpret", block_c=2)
    assert_outs_equal(ref, cand)


def test_pallas_engine_full_parity():
    """End to end: the Pallas engine in interpreter mode, asked for by name,
    is bit-identical to the scalar reference through the public surface."""
    sc = small_scenario(bids=[0.34, 0.36, 0.37])
    eng = PallasEngine(interpret=True)
    assert eng.name == "pallas"
    assert eng.impl == "interpret"
    report = assert_parity(sc, engine=eng)
    assert report.candidate.engine == "pallas"
    assert report.candidate.timings.impl == "interpret"


@pytest.mark.parametrize("make", [lambda: get_engine("pallas"), lambda: PallasEngine()])
def test_native_pallas_engine_raises_instead_of_interpreting(make):
    """Without interpret=True the engine would need a native compile, which
    the float64 kernel cannot get on the TPU: it says so rather than falling
    back to the interpreter."""
    with pytest.raises(NotImplementedError, match="does not compile natively"):
        make()

