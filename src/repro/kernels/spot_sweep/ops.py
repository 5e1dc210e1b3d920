"""Spot-sweep op: the fused (type × bid × seed) sweep on the device.

``spot_sweep_grid`` evaluates every batched scheme of a scenario over a
pre-built period grid with the one-compile multi-scheme ``lax.scan``
program (:func:`repro.kernels.spot_sweep.kernel.build_sweep_scan`), jitted
and cached per scheme set, and returns the same per-scheme output dicts as
the NumPy driver :func:`repro.engine.batch.run_schemes_numpy`, its
reference.

The program simulates on-device (states *and* per-period run records — the
billing inputs — accumulate in it) and shares the vectorized NumPy biller
with the batch backend, so costs are bit-identical where the simulation is.
The records come down as one ``uint8`` code per (period, cell); the host
rebuilds each run's end from the grid and the fetched completion times.
"""

from __future__ import annotations

import numpy as np

from repro.core.schemes import Scheme
from repro.obs import retrace
from repro.obs import telemetry as obs

#: retrace-registry scope for the fused sweep programs (detail = scheme values)
TRACE_SCOPE = "spot_sweep"

#: jitted scan program per scheme set; shared by every engine in the process
_SCAN_CACHE: dict[tuple, object] = {}


def trace_count(schemes) -> int:
    """How many times the scan program for ``schemes`` has been traced.

    Thin shim over the process-wide :mod:`repro.obs.retrace` registry (scope
    ``"spot_sweep"``); :func:`repro.obs.retrace_guard` is the general API.
    ACC never enters the device program (it runs on the host-side NumPy
    seek/lease driver), so it is filtered from the cache key here exactly as
    :func:`spot_sweep_grid` filters it from the compiled scheme set.
    """
    key = tuple(s.value for s in schemes if s is not Scheme.ACC)
    return retrace.trace_count(TRACE_SCOPE, key)


def _scan_fn(schemes, jax_mod):
    key = tuple(s.value for s in schemes)
    fn = _SCAN_CACHE.get(key)
    if fn is None:
        from repro.kernels.spot_sweep import kernel as K

        def bump(k=key):
            retrace.record_trace(TRACE_SCOPE, k)

        fn = jax_mod.jit(K.build_sweep_scan(schemes, count_cb=bump))
        _SCAN_CACHE[key] = fn
    return fn


def scan_arrays(grid, need_edge, need_adapt, t_r, adapt_tables) -> dict:
    """Host arrays of the scan program's array arguments, by keyword."""
    out = {"A_T": grid.A.T, "B_T": grid.B.T, "valid_T": grid.valid.T, "horizon": grid.horizon}
    if need_edge:
        # the per-market edge arrays expanded to the cell axis
        flat, base_m, n_m = grid.edges()
        m_of = np.arange(grid.n_cells) // grid.n_bids
        out.update(
            edges_flat=flat, edge_base=base_m[m_of], edge_n=n_m[m_of],
            ptr0_T=grid.edge_ptr0(t_r).T,
        )
    if need_adapt:
        out.update(
            tab_flat=adapt_tables.flat, tab_off=adapt_tables.off, tab_top=adapt_tables.top
        )
    return out


def scan_scalars(scenario, need_adapt, adapt_tables) -> dict:
    """The scan program's scalar arguments, by keyword (traced, so a new
    value never recompiles)."""
    params = scenario.params
    out = dict(
        init_saved=float(scenario.initial_saved_work),
        work_s=float(scenario.work_s),
        t_c=float(params.t_c),
        t_r=float(params.t_r),
        hour_delta=float(params.billing_period_s),
    )
    if need_adapt:
        out.update(
            interval=float(params.adapt_interval_s),
            bin_s=float(adapt_tables.bin_s),
            n_bins=int(adapt_tables.n_bins),
        )
    return out


def _device_arrays(grid, jnp, need_edge, need_adapt, t_r, adapt_tables, tel):
    """Device copies of :func:`scan_arrays`, memoized on the grid object
    (which :func:`repro.engine.batch.grid_and_tables` already shares per
    scenario) so repeat runs skip the host→device transfer.  A miss is
    timed as ``sim.inputs`` (host arrays) and ``sim.h2d`` (the copies up as
    the host issues them, counted in ``sweep.h2d_bytes``)."""
    key = (need_edge, need_adapt, t_r)
    cache = grid.__dict__.get("_sweep_device")
    # the tables are matched by identity: fresh tables (different bin_s,
    # pdfs) must never mix with a stale device copy
    if cache is None or cache["key"] != key or cache["tables"] is not adapt_tables:
        with tel.span("sim.inputs"):
            host = scan_arrays(grid, need_edge, need_adapt, t_r, adapt_tables)
        with tel.span("sim.h2d"):
            # not waited for: the scan waits for its inputs on the device,
            # inside ``sim.device`` (a block_until_ready here, or on the
            # outputs, cost a study 4-9% of its host time on a TPU v5e)
            arrays = {k: jnp.asarray(v) for k, v in host.items()}
        tel.count("sweep.h2d_bytes", sum(v.nbytes for v in host.values()))
        cache = grid.__dict__["_sweep_device"] = {
            "key": key,
            "tables": adapt_tables,
            "arrays": arrays,
        }
    return cache["arrays"]


def spot_sweep_grid(schemes, grid, scenario, adapt_tables=None):
    """Evaluate ``schemes`` over a :class:`~repro.engine.batch._PeriodGrid`.

    Returns ``(outs, info)``: ``outs`` maps each scheme to the standard
    output dict (``completed`` / ``completion_time`` / ``cost`` /
    ``n_checkpoints`` / ``n_kills`` / ``work_lost_s``), ``info`` carries the
    ``impl`` label ``"scan"``.  The sim vs billing phase split is recorded as
    telemetry spans (``sim`` with an ``impl`` attr, ``bill`` per scheme) on
    the active collector — :class:`repro.engine.base.PhaseTimings` folds
    them for the benchmark's ``--profile`` view.  The scan's phases are
    child spans of ``sim``: ``sim.inputs`` and ``sim.h2d`` (on a device-copy
    miss), ``sim.device`` (the program, from dispatch until its first output
    is on the host, so also the copies up still in flight) and ``sim.fetch``
    (the other copies down: the finals and one ``uint8`` record code per
    (period, cell) a scheme, all of them counted in ``sweep.d2h_bytes``).
    """
    impl = "scan"
    schemes = tuple(schemes)
    tel = obs.current()
    outs: dict[Scheme, dict] = {}
    if Scheme.ACC in schemes:
        # ACC is not period-structured (host-side seek/lease state machine):
        # it runs on the NumPy driver and the program fuses the rest.
        # A pure-ACC scheme set never touches jax at all.
        from repro.engine.batch import _run_acc

        with tel.span("sim", scheme=Scheme.ACC.value, impl="ref"):
            outs[Scheme.ACC] = _run_acc(grid, scenario)
        schemes = tuple(s for s in schemes if s is not Scheme.ACC)
        if not schemes:
            return outs, {"impl": impl}

    from repro.engine.jax_backend import _require_jax

    jax_mod, jnp, _ = _require_jax()
    from repro.engine.batch import _bill_runs_flat

    params = scenario.params
    delta = float(params.billing_period_s)
    need_edge = Scheme.EDGE in schemes
    need_adapt = Scheme.ADAPT in schemes
    S = len(schemes)

    with tel.span("sim", impl=impl):
        kwargs = scan_scalars(scenario, need_adapt, adapt_tables)
        kwargs.update(
            _device_arrays(grid, jnp, need_edge, need_adapt, params.t_r, adapt_tables, tel)
        )
        with tel.span("sim.device"):
            pairs = _scan_fn(schemes, jax_mod)(**kwargs)
            # copying one output down waits for the whole program, as the
            # fetch below would; the array keeps that host copy
            np.asarray(pairs[0][0][1])
        with tel.span("sim.fetch"):
            finals = [
                # state = (saved, done, comp_time, n_ckpt, work_lost, has_run, n_kills)
                tuple(np.asarray(pairs[si][0][j]) for j in (1, 2, 3, 4, 6))
                for si in range(S)
            ]
            codes = [np.asarray(pairs[si][1]) for si in range(S)]  # (P, C) uint8
        moved = [a for f in finals for a in f] + codes
        tel.count("sweep.d2h_bytes", sum(a.nbytes for a in moved))

    for si, scheme in enumerate(schemes):
        with tel.span("bill", scheme=scheme.value):
            done, comp_time, n_ckpt, work_lost, n_kills = finals[si]
            # kernel.record_code: bit 0 a run ends, bit 1 the user ended it
            # there, at comp_time; every other run ends at its period's end
            code = codes[si]
            pp, cc = np.nonzero(code & 1)
            user = (code[pp, cc] & 2) != 0
            end = np.where(user, comp_time[cc], grid.B[cc, pp])
            total, _ = _bill_runs_flat(grid, pp, cc, grid.A[cc, pp], end, user, delta)
            outs[scheme] = {
                "completed": done & np.isfinite(comp_time),
                "completion_time": comp_time,
                "cost": total,
                "n_checkpoints": n_ckpt,
                "n_kills": n_kills,  # accumulated on-device, not re-derived here
                "work_lost_s": work_lost,
            }
    return outs, {"impl": impl}
