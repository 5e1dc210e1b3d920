"""Spot-sweep op: backend dispatch for the fused (type × bid × seed) sweep.

``spot_sweep_grid`` evaluates every batched scheme of a scenario over a
pre-built period grid and returns the same per-scheme output dicts as the
NumPy driver, whatever the implementation:

  * ``"ref"`` — the NumPy lockstep driver in :mod:`repro.engine.batch`
    (the triad's bit-exact reference; no jax required).
  * ``"scan"`` — the one-compile multi-scheme ``lax.scan`` program
    (:func:`repro.kernels.spot_sweep.kernel.build_sweep_scan`), jitted and
    cached per scheme set; the default, on the TPU as elsewhere.
  * ``"interpret"`` — the fused Pallas kernel in interpreter mode (CPU
    parity suite; slow, test-sized grids only).  It has no native impl:
    see :data:`repro.kernels.spot_sweep.kernel.NATIVE_UNSUPPORTED`.

Device impls simulate on-device (states *and* per-period run records — the
billing inputs — accumulate in the program) and share the vectorized NumPy
biller with the batch backend, so costs are bit-identical across every impl.
"""

from __future__ import annotations

import numpy as np

from repro.core.schemes import Scheme
from repro.obs import retrace
from repro.obs import telemetry as obs

_FORCE_IMPL: str | None = None

#: retrace-registry scope for the fused sweep programs (detail = scheme values)
TRACE_SCOPE = "spot_sweep"

#: jitted scan program per scheme set; shared by every engine in the process
_SCAN_CACHE: dict[tuple, object] = {}


def set_impl(impl: str | None) -> None:
    global _FORCE_IMPL
    _FORCE_IMPL = impl


def _default_impl() -> str:
    return _FORCE_IMPL if _FORCE_IMPL is not None else "scan"


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


def _edge_inputs(grid, t_r):
    """Per-cell EDGE sweep inputs ``(edges_flat, edge_base, edge_n, ptr0)``
    — the one place the per-market edge arrays expand to the cell axis."""
    flat, base_m, n_m = grid.edges()
    m_of = np.arange(grid.n_cells) // grid.n_bids
    return flat, base_m[m_of], n_m[m_of], grid.edge_ptr0(t_r)


def scan_arrays(grid, need_edge, need_adapt, t_r, adapt_tables) -> dict:
    """Host arrays of the scan program's array arguments, by keyword."""
    out = {"A_T": grid.A.T, "B_T": grid.B.T, "valid_T": grid.valid.T, "horizon": grid.horizon}
    if need_edge:
        flat, base, n, ptr0 = _edge_inputs(grid, t_r)
        out.update(edges_flat=flat, edge_base=base, edge_n=n, ptr0_T=ptr0.T)
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


def spot_sweep_grid(
    schemes,
    grid,
    scenario,
    adapt_tables=None,
    impl: str | None = None,
    block_c: int = 256,
):
    """Evaluate ``schemes`` over a :class:`~repro.engine.batch._PeriodGrid`.

    Returns ``(outs, info)``: ``outs`` maps each scheme to the standard
    output dict (``completed`` / ``completion_time`` / ``cost`` /
    ``n_checkpoints`` / ``n_kills`` / ``work_lost_s``), ``info`` carries the
    resolved ``impl`` label.  The sim vs billing phase split is recorded as
    telemetry spans (``sim`` with an ``impl`` attr, ``bill`` per scheme) on
    the active collector — :class:`repro.engine.base.PhaseTimings` folds
    them for the benchmark's ``--profile`` view.
    """
    schemes = tuple(schemes)
    if impl is None:
        impl = _default_impl()
    if impl == "ref":
        from repro.engine.batch import run_schemes_numpy

        return run_schemes_numpy(schemes, grid, scenario, adapt_tables)

    tel = obs.current()
    outs: dict[Scheme, dict] = {}
    if Scheme.ACC in schemes:
        # ACC is not period-structured (host-side seek/lease state machine):
        # every device impl routes it to the NumPy driver and fuses the rest.
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
        finals, recs_np = _run_device(
            impl, schemes, grid, scenario, adapt_tables, jax_mod, jnp,
            need_edge, need_adapt, delta, S, block_c, tel,
        )

    for si, scheme in enumerate(schemes):
        with tel.span("bill", scheme=scheme.value):
            done, comp_time, n_ckpt, work_lost, n_kills = finals[si]
            exists, end, user = recs_np[si]
            pp, cc = np.nonzero(exists)
            total, _ = _bill_runs_flat(
                grid, pp, cc, grid.A[cc, pp], end[pp, cc], user[pp, cc], delta
            )
            outs[scheme] = {
                "completed": done & np.isfinite(comp_time),
                "completion_time": comp_time,
                "cost": total,
                "n_checkpoints": n_ckpt,
                "n_kills": n_kills,  # accumulated on-device, not re-derived here
                "work_lost_s": work_lost,
            }
    return outs, {"impl": impl}


def _run_device(
    impl, schemes, grid, scenario, adapt_tables, jax_mod, jnp,
    need_edge, need_adapt, delta, S, block_c, tel,
):
    """Dispatch the fused device sweep; returns per-scheme final states and
    run records as host arrays.  The scan's phases are timed as child spans
    of ``sim``: ``sim.inputs`` and ``sim.h2d`` (on a device-copy miss),
    ``sim.device`` (the program, from dispatch until its first output is on
    the host, so also the copies up still in flight) and ``sim.fetch`` (the
    other copies down; all of them counted in ``sweep.d2h_bytes``)."""
    params = scenario.params
    if impl == "scan":
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
            recs_np = [tuple(np.asarray(x) for x in pairs[si][1]) for si in range(S)]  # (P, C)
        tel.count("sweep.d2h_bytes", sum(a.nbytes for f in finals + recs_np for a in f))
    elif impl == "interpret":
        from repro.kernels.spot_sweep import kernel as K

        consts = dict(
            init_saved=float(scenario.initial_saved_work),
            work_s=float(scenario.work_s),
            t_c=float(params.t_c),
            t_r=float(params.t_r),
            hour_delta=delta,
            interval=float(params.adapt_interval_s),
            bin_s=float(adapt_tables.bin_s) if adapt_tables is not None else 0.0,
            n_bins=int(adapt_tables.n_bins) if adapt_tables is not None else 1,
        )
        edges = ptr0 = tables = None
        if need_edge:
            flat, base, n, ptr0 = _edge_inputs(grid, params.t_r)
            edges = (flat, base, n)
        if need_adapt:
            tables = (adapt_tables.flat, adapt_tables.off, adapt_tables.top)
        out = K.sweep_pallas(
            schemes, grid.A, grid.B, grid.valid, grid.horizon, consts,
            ptr0=ptr0, edges=edges, tables=tables, block_c=block_c,
        )
        done, comp, ckpt, lost, kills, rex, rend, ruser = (np.asarray(x) for x in out)
        finals = [(done[si], comp[si], ckpt[si], lost[si], kills[si]) for si in range(S)]
        recs_np = [(rex[si].T, rend[si].T, ruser[si].T) for si in range(S)]
    else:
        raise ValueError(f"unknown spot_sweep impl {impl!r}")
    return finals, recs_np
