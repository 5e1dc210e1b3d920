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


def _step_records(sc, grid, tables):
    """Per scheme, ``(exists, end, user)`` as the program's building blocks
    return them: ``period_step_masked`` (through ``scheme_period_step``) one
    period at a time for NONE / OPT / HOUR / EDGE, ``_adapt_decoupled`` for
    ADAPT."""
    import jax
    import jax.numpy as jnp

    from repro.engine.jax_backend import _require_jax
    from repro.kernels.spot_sweep import kernel as K

    _require_jax()  # float64 on, as the program runs
    host = sweep_ops.scan_arrays(grid, True, True, sc.params.t_r, tables)
    d = {k: jnp.asarray(v) for k, v in host.items()}
    s = sweep_ops.scan_scalars(sc, True, tables)
    c = {k: s[k] for k in ("work_s", "t_c", "t_r", "hour_delta")}
    c.update({k: d[k] for k in ("edges_flat", "edge_base", "edge_n")})
    out = {}
    for scheme in sc.schemes:
        if scheme is Scheme.ADAPT:
            _, recs = K._adapt_decoupled(
                d["A_T"].T, d["B_T"].T, d["valid_T"].T, d["horizon"], s["init_saved"],
                s["work_s"], s["t_c"], s["t_r"], s["interval"], d["tab_flat"], d["tab_off"],
                d["tab_top"], s["bin_s"], s["n_bins"],
            )
            out[scheme] = tuple(np.asarray(x) for x in recs)
            continue
        step = jax.jit(lambda st, a, b, v, ptr0, scheme=scheme: K.scheme_period_step(
            scheme, st, a, b, v, d["horizon"], ptr0, c))
        state = K.init_state(grid.n_cells, s["init_saved"])
        recs = []
        for p in range(grid.A.shape[1]):
            state, rec = step(state, d["A_T"][p], d["B_T"][p], d["valid_T"][p], d["ptr0_T"][p])
            recs.append([np.asarray(x) for x in rec])
        out[scheme] = tuple(np.stack(x) for x in zip(*recs))
    return out


def test_record_codes_rebuild_the_run_records():
    """The program returns one record code per (period, cell); the host
    rebuilds each run's end as ``comp_time`` where the user ended it and
    ``B`` elsewhere.  That holds only while every scheme ends its runs
    there, which this pins against the building blocks themselves."""
    import jax

    sc = small_scenario(days=20, seed=7, work_h=12.0, bids=[0.30, 0.345, 0.36, 0.40])
    assert set(sc.schemes) == {Scheme.NONE, Scheme.OPT, Scheme.HOUR, Scheme.EDGE, Scheme.ADAPT}
    cand, _ = run_scan(sc)
    assert_outs_equal(run_ref(sc), cand)

    grid, tables = _grid(sc)
    kwargs = sweep_ops.scan_scalars(sc, True, tables)
    kwargs.update(sweep_ops.scan_arrays(grid, True, True, sc.params.t_r, tables))
    pairs = sweep_ops._scan_fn(sc.schemes, jax)(**kwargs)
    steps = _step_records(sc, grid, tables)
    for scheme, (state, code) in zip(sc.schemes, pairs):
        code = np.asarray(code)
        assert code.dtype == np.uint8 and code.shape == grid.B.T.shape
        comp_time = np.asarray(state[2])
        user = (code & 2) != 0
        rebuilt = ((code & 1) != 0, np.where(user, comp_time[None, :], grid.B.T), user)
        exists, end, rec_user = steps[scheme]
        assert rec_user.any() and (exists & ~rec_user).any(), scheme  # both ends occur
        for name, got, want in zip(("exists", "end", "user"), rebuilt, (exists, end, rec_user)):
            np.testing.assert_array_equal(got, want, err_msg=f"{scheme.value}.{name}")
