"""Batch backend: structure-of-arrays lockstep evaluation of a Scenario.

Lowers every bid-limited scheme (NONE / OPT / HOUR / EDGE / ADAPT) onto NumPy
ops over the flattened ``(market, bid)`` cell axis: availability periods are
padded into ``(cells, periods)`` arrays, and the engine walks *period index*
(outer) and *checkpoint-window / decision-tick index* (inner) sequentially
while every cell of the grid advances in lockstep.  Nested Python loops over
cells disappear; what remains is O(max periods × max windows) vector steps
over the whole grid.

The per-scheme math lives in :mod:`repro.engine.kernels` as pure functions
that take their array namespace as an argument; this module owns the NumPy
driver — the period grid, the compressed active-cell bookkeeping, and the
fully vectorized billing (runs sorted by (cell, period), ``np.add.at``
accumulating the scalar's chronological cost sums bit-exactly — no
per-period host loop).  The period grid and ADAPT tables are cached per
scenario object (:func:`grid_and_tables`) and shared by every array backend
in the process; this driver doubles as the ``impl="ref"`` path of the
:mod:`repro.kernels.spot_sweep` triad.  ADAPT's per-step hazard decision is precomputed into
binned survival tables per (market, bid) cell (:class:`AdaptTables`), so it
advances in lockstep like the other schemes instead of falling back to the
scalar loop.  ACC — a different control loop entirely (bid-unlimited leases,
poll-driven relaunch) — runs as a cell-decoupled seek/lease state machine
(:func:`_run_acc`) over the same period grid, each seek resolved in one step
from a per-period launch-tick table wherever the poll lattice is exact, so no
scheme falls back to the per-cell scalar path anymore.

Exactness is the design contract, not an aspiration (see
:mod:`repro.engine.kernels` and :mod:`repro.engine.parity`): parity with the
scalar reference is asserted ``==``, not ``allclose``.
"""

from __future__ import annotations

import math
import time
import weakref
from fractions import Fraction

import numpy as np

from repro.core.schemes import Scheme
from repro.core.simulator import _poll_walk
from repro.engine.base import EngineResult, PhaseTimings, empty_result, fold_result_counters
from repro.engine.kernels import (
    _EPS,
    AdaptTables,
    _kernel_none,
    _kernel_opt,
    _kernel_windows,
    acc_lease_tick,
)
from repro.engine.scenario import MarketCell, Scenario
from repro.obs import telemetry as obs

#: Per-scenario cache of the derived simulation inputs (period grid, ADAPT
#: decision tables) shared by *every* array backend in the process: running
#: the same Scenario object on batch, then jax builds the grid and tables
#: exactly once.  Keys are weak — the cache dies with the scenario.
_SCENARIO_CACHE: "weakref.WeakKeyDictionary[Scenario, dict]" = weakref.WeakKeyDictionary()


def grid_and_tables(
    scenario: Scenario, markets: list[MarketCell], need_adapt: bool
) -> tuple["_PeriodGrid", AdaptTables | None]:
    """The (cached) period grid + ADAPT tables for a scenario.

    Both are pure functions of the scenario (materialization is
    deterministic), so one build serves every backend and every re-run in the
    process."""
    tel = obs.current()
    entry = _SCENARIO_CACHE.setdefault(scenario, {})
    if "grid" not in entry:
        with tel.span("grid.periods"):
            entry["grid"] = _PeriodGrid.build(markets, scenario)
    if need_adapt and "tables" not in entry:
        with tel.span("grid.adapt_tables"):
            entry["tables"] = AdaptTables.build(markets, scenario, entry["grid"])
    return entry["grid"], entry.get("tables")


def run_batched(scenario: Scenario, engine_name: str, run_schemes) -> EngineResult:
    """Shared driver for the array backends (batch, jax).

    Materializes the market, resolves the cached period grid + ADAPT decision
    tables, and dispatches the whole scheme set to ``run_schemes(schemes,
    grid, scenario, adapt_tables)`` — one call, so a backend may evaluate
    every scheme in a single compiled program.  Every scheme is batched
    (``BATCHED_SCHEMES`` covers ACC too).  The backends can never drift in
    their orchestration, only in their kernels.

    Every phase is timed as a telemetry span (``grid`` / ``sim`` / ``bill``
    under one ``engine.run`` root); the span tree lands in the
    active :class:`~repro.obs.telemetry.Telemetry` collector when there is
    one — a throwaway local collector otherwise — and is folded into the
    typed :class:`~repro.engine.base.PhaseTimings` on
    ``EngineResult.timings`` either way.  ``run_schemes`` returns ``(outs,
    info)``: per-scheme output dicts plus a small free-form dict (the
    ``impl`` label) that the kernel test suite reads directly.
    """
    markets = scenario.materialize()
    amb = obs.current()
    tel = amb if amb.enabled else obs.Telemetry()  # local phase recorder
    t0 = time.perf_counter()  # wall_s measures simulation, not trace gen
    res = empty_result(scenario, markets, engine_name)

    with obs.activate(tel), tel.span("engine.run", engine=engine_name) as root:
        schemes = tuple(scenario.schemes)
        with tel.span("grid"):
            grid, adapt_tables = grid_and_tables(scenario, markets, Scheme.ADAPT in schemes)
        outs, _info = run_schemes(schemes, grid, scenario, adapt_tables)
        M, B = len(markets), len(scenario.bids)
        for scheme, out in outs.items():
            s = scenario.schemes.index(scheme)
            res.completed[:, :, s] = out["completed"].reshape(M, B)
            res.completion_time[:, :, s] = out["completion_time"].reshape(M, B)
            res.cost[:, :, s] = out["cost"].reshape(M, B)
            res.n_checkpoints[:, :, s] = out["n_checkpoints"].reshape(M, B)
            res.n_kills[:, :, s] = out["n_kills"].reshape(M, B)
            res.work_lost_s[:, :, s] = out["work_lost_s"].reshape(M, B)
            if "n_self_terminations" in out:
                res.n_self_terminations[:, :, s] = out["n_self_terminations"].reshape(M, B)

    res.wall_s = time.perf_counter() - t0
    res.timings = PhaseTimings.from_span(root, engine_name, res.wall_s)
    if amb.enabled:
        fold_result_counters(amb, res)
    return res


def run_schemes_numpy(schemes, grid, scenario, adapt_tables):
    """NumPy evaluation of a batched scheme set, one driver pass per scheme.
    Also the ``impl="ref"`` path of the ``spot_sweep`` kernel triad."""
    tel = obs.current()
    outs: dict[Scheme, dict] = {}
    for scheme in schemes:
        with tel.span("sim", scheme=scheme.value):
            outs[scheme] = _run_scheme(scheme, grid, scenario, adapt_tables)
    return outs, {"impl": "ref"}


class BatchEngine:
    """Vectorized evaluation; bit-identical to :class:`ReferenceEngine` on
    cost / completion_time / n_kills / n_checkpoints for every scheme,
    ACC included."""

    name = "batch"

    def run(self, scenario: Scenario) -> EngineResult:
        return run_batched(scenario, self.name, run_schemes_numpy)


# ---------------------------------------------------------------------------
# Period grid: padded (cells, periods) SoA view of availability
# ---------------------------------------------------------------------------


class _PeriodGrid:
    """Flattened cell axis ``c = m * n_bids + b`` with padded period arrays.

    ``A[c, p]`` / ``B[c, p]`` are the start/end of cell ``c``'s ``p``-th
    availability period (NaN pad), ``valid[c, p]`` marks real periods,
    ``horizon[c]`` is the owning trace's horizon.
    """

    def __init__(self, markets, bids, A, B, valid, horizon):
        self.markets = markets
        self.bids = bids
        self.A = A
        self.B = B
        self.valid = valid
        self.horizon = horizon
        self.n_markets = len(markets)
        self.n_bids = len(bids)
        self.n_cells = A.shape[0]
        # lazy EDGE support: (per-market edge arrays, flat, base, counts)
        self._edges: tuple | None = None
        self._edge_ptr0: np.ndarray | None = None
        # lazy ACC launch tables, keyed by poll period
        self._acc_launch: dict[float, tuple[np.ndarray, np.ndarray]] = {}

    @staticmethod
    def build(markets: list[MarketCell], scenario: Scenario) -> "_PeriodGrid":
        per_market = [
            _periods_all_bids(cellm.trace, scenario.market_bids(cellm)) for cellm in markets
        ]
        counts = np.concatenate([c for _, _, c in per_market])
        C = len(counts)
        P = max(int(counts.max()), 1) if C else 1
        A = np.full((C, P), np.nan)
        B = np.full((C, P), np.nan)
        valid = np.zeros((C, P), dtype=bool)
        row0 = 0
        for a_flat, b_flat, cnt in per_market:
            n = len(cnt)
            if a_flat.size:
                # row-major flat (cell, period-within-cell) scatter
                rows = np.repeat(np.arange(n), cnt)
                cols = np.arange(len(a_flat)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
                A[row0 + rows, cols] = a_flat
                B[row0 + rows, cols] = b_flat
                valid[row0 + rows, cols] = True
            row0 += n
        horizon = np.repeat([m.trace.horizon for m in markets], len(scenario.bids))
        return _PeriodGrid(markets, tuple(scenario.bids), A, B, valid, horizon)

    def edges(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(edges_flat, base_of_market, n_edges_of_market) for EDGE windows."""
        if self._edges is None:
            per_market = [m.trace.rising_edges().astype(np.float64) for m in self.markets]
            n = np.asarray([len(e) for e in per_market], dtype=np.int64)
            base = np.concatenate(([0], np.cumsum(n)[:-1]))
            # keep at least one element: masked gathers index 0 unconditionally
            flat = np.concatenate(per_market) if n.sum() else np.zeros(1)
            self._edges = (per_market, flat, base, n)
        _, flat, base, n = self._edges
        return flat, base, n

    def edge_ptr0(self, t_r: float) -> np.ndarray:
        """(cells, periods) cursor table: index of the first rising edge
        strictly after each period's ``start_work = A + t_r`` (one
        ``searchsorted`` per market; NaN pads sort past every edge)."""
        if self._edge_ptr0 is None:
            self.edges()
            per_market = self._edges[0]
            ptr = np.empty(self.A.shape, dtype=np.int64)
            for m, sl in self.market_slices():
                block = self.A[sl] + t_r
                ptr[sl] = np.searchsorted(per_market[m], block.ravel(), side="right").reshape(
                    block.shape
                )
            self._edge_ptr0 = ptr
        return self._edge_ptr0

    def acc_launch(self, poll: float) -> tuple[np.ndarray, np.ndarray]:
        """ACC's launch table on the poll lattice ``k * poll``: ``(TK, NXT)``.

        ``TK[c, p]`` is the first lattice tick at or after ``A[c, p]`` — the
        tick ``_next_launch_time`` reaches first inside period ``p`` (the
        ``ceil(x / poll - eps)`` tick falls one step short when ``A`` lies
        within ``eps * poll`` above a tick).  ``NXT[c, q]`` (``P + 1``
        columns) is the first period ``p >= q`` whose tick lands inside it
        (``TK < B``), or ``P`` when none does; NaN pads never qualify."""
        if poll not in self._acc_launch:
            C, P = self.A.shape
            TK = np.ceil(self.A / poll - _EPS) * poll
            TK = np.where(TK < self.A, TK + poll, TK)
            lands = np.where(self.valid & (TK < self.B), np.arange(P), P)
            NXT = np.full((C, P + 1), P, dtype=np.int64)
            NXT[:, :P] = np.minimum.accumulate(lands[:, ::-1], axis=1)[:, ::-1]
            self._acc_launch[poll] = (TK, NXT)
        return self._acc_launch[poll]

    def edge_state(self, cells: np.ndarray, period: int, t_r: float):
        """Per-cell edge cursors for :func:`_kernel_windows` (EDGE mode):
        ``(edges_flat, base, n_edges, ptr)``."""
        flat, base_m, n_m = self.edges()
        m_of = cells // self.n_bids
        return flat, base_m[m_of], n_m[m_of], self.edge_ptr0(t_r)[cells, period]

    def market_slices(self):
        """Contiguous cell ranges per market (cells are market-major)."""
        for m in range(self.n_markets):
            yield m, slice(m * self.n_bids, (m + 1) * self.n_bids)


def _periods_all_bids(trace, bids) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized ``available_periods`` for every bid at once.

    Returns ``(starts_flat, ends_flat, counts)``: period start/end times
    concatenated bid-major (periods of bid 0, then bid 1, ...), chronological
    within each bid, plus the per-bid period count.  Values are read from
    ``trace.times`` exactly as the scalar ``available_periods`` does, so the
    floats are identical.
    """
    bids_arr = np.asarray(bids, dtype=np.float64)
    ok = trace.prices[None, :] <= bids_arr[:, None]  # (B, N)
    Bn, N = ok.shape
    d = np.diff(ok.astype(np.int8), axis=1)
    rs, cs = np.nonzero(d == 1)
    re_, ce = np.nonzero(d == -1)
    # prepend col-0 starts / append col-N ends for bids available at the rims
    first = np.nonzero(ok[:, 0])[0]
    last = np.nonzero(ok[:, -1])[0]
    start_rows = np.concatenate([rs, first])
    start_cols = np.concatenate([cs + 1, np.zeros(len(first), dtype=np.int64)])
    end_rows = np.concatenate([re_, last])
    end_cols = np.concatenate([ce + 1, np.full(len(last), N, dtype=np.int64)])
    so = np.lexsort((start_cols, start_rows))
    eo = np.lexsort((end_cols, end_rows))
    counts = np.bincount(start_rows, minlength=Bn)
    return trace.times[start_cols[so]], trace.times[end_cols[eo]], counts


# ---------------------------------------------------------------------------
# NumPy driver — walks periods, dispatching to the pure kernels
# ---------------------------------------------------------------------------


def _run_scheme(
    scheme: Scheme,
    grid: _PeriodGrid,
    scenario: Scenario,
    adapt_tables: AdaptTables | None = None,
) -> dict[str, np.ndarray]:
    if scheme == Scheme.ADAPT:
        # ADAPT's decision cadence (~10 min) makes its periods an order of
        # magnitude more iterations than HOUR's windows, so it gets a
        # cell-decoupled driver: every cell walks its *own* (period, tick)
        # cursor and the loop count is the busiest cell's tick total, not the
        # per-period maximum summed over the padded period axis.
        return _run_adapt(grid, scenario, adapt_tables)
    if scheme == Scheme.ACC:
        # ACC is not period-structured (bid-unlimited leases, poll-driven
        # relaunch): a cell-decoupled seek/lease state machine over the same
        # period grid, with per-lane monotone period cursors answering every
        # price-vs-bid query.
        return _run_acc(grid, scenario)
    params = scenario.params
    work_s = scenario.work_s
    t_r, t_c, delta = params.t_r, params.t_c, params.billing_period_s
    C, P = grid.A.shape

    saved = np.full(C, float(scenario.initial_saved_work))
    none_reset = scheme == Scheme.NONE
    has_run = np.zeros(C, dtype=bool) if none_reset else None
    done = np.zeros(C, dtype=bool)
    comp_time = np.full(C, np.inf)
    n_ckpt = np.zeros(C, dtype=np.int64)
    work_lost = np.zeros(C)
    # run records: (period, cell indices, launch, end, user-terminated)
    runs: list[tuple[int, np.ndarray, np.ndarray, np.ndarray, bool]] = []

    for p in range(P):
        # compress to cells with a live p-th availability period: the period
        # tail is driven by a few low-bid cells, so later iterations shrink
        act = np.nonzero(grid.valid[:, p] & ~done)[0]
        if act.size == 0:
            continue
        a = grid.A[act, p]
        b = grid.B[act, p]
        start_work = a + t_r
        if none_reset:
            # NONE restarts from scratch after any recorded run
            saved[act[has_run[act]]] = 0.0

        short = start_work >= b
        if short.any():
            shortk = short & (b < grid.horizon[act])
            if shortk.any():
                idx = act[shortk]
                runs.append((p, idx, a[shortk], b[shortk], False))
                if none_reset:
                    has_run[idx] = True
            go = ~short
            act, a, b, start_work = act[go], a[go], b[go], start_work[go]
            if act.size == 0:
                continue
        sv = saved[act]
        if scheme == Scheme.NONE:
            out = _kernel_none(np, b, start_work, sv, work_s)
        elif scheme == Scheme.OPT:
            out = _kernel_opt(np, b, start_work, sv, work_s, t_c)
        elif scheme == Scheme.HOUR:
            out = _kernel_windows(np, a, b, start_work, sv, work_s, t_c, hour_delta=delta)
        elif scheme == Scheme.EDGE:
            out = _kernel_windows(
                np, a, b, start_work, sv, work_s, t_c, edge_state=grid.edge_state(act, p, t_r)
            )
        else:  # pragma: no cover - guarded by BATCHED_SCHEMES
            raise ValueError(f"no batch kernel for {scheme}")
        done_now, done_at, work_end, saved_out, ckpt_add = out

        n_ckpt[act] += ckpt_add
        if done_now.any():
            comp_idx = act[done_now]
            comp_time[comp_idx] = done_at[done_now]
            done[comp_idx] = True
            runs.append((p, comp_idx, a[done_now], done_at[done_now], True))

        kl = ~done_now
        if kl.any():
            kl_idx = act[kl]
            runs.append((p, kl_idx, a[kl], b[kl], False))
            if none_reset:
                work_lost[kl_idx] += work_end[kl] - 0.0
                has_run[kl_idx] = True
            else:
                work_lost[kl_idx] += work_end[kl] - saved_out[kl]
                saved[kl_idx] = saved_out[kl]

    with obs.current().span("bill", scheme=scheme.value):
        total, n_kills = _bill_runs(grid, runs, delta)

    return {
        "completed": done & np.isfinite(comp_time),
        "completion_time": comp_time,
        "cost": total,
        "n_checkpoints": n_ckpt,
        "n_kills": n_kills,
        "work_lost_s": work_lost,
    }


# ---------------------------------------------------------------------------
# ADAPT driver — cell-decoupled lockstep over (period, decision-tick) cursors
# ---------------------------------------------------------------------------


def _run_adapt(
    grid: _PeriodGrid, scenario: Scenario, tables: AdaptTables
) -> dict[str, np.ndarray]:
    """Walk every ADAPT cell through its own periods and decision ticks in
    one lockstep loop.

    Unlike the shared period-synchronized driver (where iteration count is
    the per-period tick *maximum summed over the padded period axis*), each
    cell here advances its own ``(period, tick)`` cursor, so the loop runs
    for the busiest single cell's tick total — ~5x fewer iterations on
    catalog grids.  The per-tick math is the one shared body
    :func:`repro.engine.kernels.adapt_tick_core`, so results stay
    bit-identical to the scalar reference.  The active set is compacted as
    cells finish.
    """
    from repro.engine.kernels import adapt_tick_core

    params = scenario.params
    work_s = scenario.work_s
    t_r, t_c, delta = params.t_r, params.t_c, params.billing_period_s
    interval = params.adapt_interval_s
    C, P = grid.A.shape

    done = np.zeros(C, dtype=bool)
    comp_time = np.full(C, np.inf)
    n_ckpt = np.zeros(C, dtype=np.int64)
    work_lost = np.zeros(C)
    # flat run records (period, cell, launch, end, user) — order-free billing
    Rp: list[np.ndarray] = []
    Rc: list[np.ndarray] = []
    Ra: list[np.ndarray] = []
    Re: list[np.ndarray] = []
    Ru: list[np.ndarray] = []

    def record(pv, cv, av, ev, user: bool) -> None:
        Rp.append(pv)
        Rc.append(cv)
        Ra.append(av)
        Re.append(ev)
        Ru.append(np.full(len(cv), user, dtype=bool))

    counts = grid.valid.sum(axis=1)
    idx = np.nonzero(counts > 0)[0]  # global cell ids of the active set
    N = len(idx)
    if N:
        cnt = counts[idx]
        hor = grid.horizon[idx]
        off = tables.off[idx]
        top = tables.top[idx]
        saved = np.full(N, float(scenario.initial_saved_work))
        p = np.zeros(N, dtype=np.int64)  # per-cell period cursor
        alive = np.ones(N, dtype=bool)
        entering = np.ones(N, dtype=bool)  # needs period-entry processing
        t = np.zeros(N)
        work = np.zeros(N)
        sv = np.zeros(N)
        next_dec = np.zeros(N)
        a_cur = np.zeros(N)
        b_cur = np.zeros(N)

        while alive.any():
            # -- enter cells into their next live period (consuming shorts)
            ent = alive & entering
            while ent.any():
                no_more = ent & (p >= cnt)
                alive &= ~no_more
                ent &= ~no_more
                if not ent.any():
                    break
                pc = np.minimum(p, cnt - 1)  # masked rows gather safely
                a = grid.A[idx, pc]
                b = grid.B[idx, pc]
                start_work = a + t_r
                short = ent & (start_work >= b)
                shortk = short & (b < hor)
                if shortk.any():
                    # killed before recovery finished: billed, no progress
                    record(p[shortk], idx[shortk], a[shortk], b[shortk], False)
                go = ent & ~short
                t = np.where(go, start_work, t)
                work = np.where(go, saved, work)
                sv = np.where(go, saved, sv)
                next_dec = np.where(go, start_work + interval, next_dec)
                a_cur = np.where(go, a, a_cur)
                b_cur = np.where(go, b, b_cur)
                entering &= ~go
                p = np.where(short, p + 1, p)
                ent = short  # short cells try their next period
            live = alive & ~entering
            if not live.any():
                continue

            # -- one decision tick (kernels.adapt_tick_core, the shared body)
            live, t, work, sv, next_dec, d_at, fin, ck, kl = adapt_tick_core(
                np, live, t, work, sv, next_dec, a_cur, b_cur, work_s, t_c,
                t_r, interval, tables.flat, off, top, tables.bin_s, tables.n_bins,
            )
            if fin.any():
                rows = idx[fin]
                comp_time[rows] = d_at[fin]
                done[rows] = True
                record(p[fin], rows, a_cur[fin], d_at[fin], True)
                alive &= ~fin
            if ck.any():
                n_ckpt[idx[ck]] += 1

            if kl.any():
                rows = idx[kl]
                record(p[kl], rows, a_cur[kl], b_cur[kl], False)
                work_lost[rows] += work[kl] - sv[kl]
                saved = np.where(kl, sv, saved)
                p = np.where(kl, p + 1, p)
                entering |= kl

            # -- compact: drop finished cells so the tail runs on small arrays
            na = int(alive.sum())
            if na and na <= N // 2:
                obs.current().count("adapt.compactions")
                keep = alive
                idx, cnt, hor, off, top = idx[keep], cnt[keep], hor[keep], off[keep], top[keep]
                saved, p, t, work, sv = saved[keep], p[keep], t[keep], work[keep], sv[keep]
                next_dec, a_cur, b_cur = next_dec[keep], a_cur[keep], b_cur[keep]
                entering = entering[keep]
                alive = np.ones(na, dtype=bool)
                N = na

    with obs.current().span("bill", scheme=Scheme.ADAPT.value):
        if Rc:
            total, n_kills = _bill_runs_flat(
                grid,
                np.concatenate(Rp),
                np.concatenate(Rc),
                np.concatenate(Ra),
                np.concatenate(Re),
                np.concatenate(Ru),
                delta,
            )
        else:
            total, n_kills = np.zeros(C), np.zeros(C, dtype=np.int64)

    return {
        "completed": done & np.isfinite(comp_time),
        "completion_time": comp_time,
        "cost": total,
        "n_checkpoints": n_ckpt,
        "n_kills": n_kills,
        "work_lost_s": work_lost,
    }


# ---------------------------------------------------------------------------
# ACC driver — cell-decoupled seek/lease state machine over poll ticks
# ---------------------------------------------------------------------------


def _run_acc(grid: _PeriodGrid, scenario: Scenario) -> dict[str, np.ndarray]:
    """Walk every ACC cell through its lease chain in one lockstep loop.

    ACC (paper §VI) is not period-structured: an instance launches at the
    first admissible poll tick, is never provider-killed, and walks hour
    boundaries to completion, self-termination, or the horizon
    (``simulator._simulate_acc``).  Each lane is one (market, bid) cell in
    one of two modes — *seeking* (``_next_launch_time``'s poll walk) or
    *in-lease* (hour ticks via :func:`repro.engine.kernels.acc_lease_tick`,
    the leased-work variant of ``windows_advance``).  Every pass of the loop
    resolves each seeking lane's whole walk and takes one hour tick for each
    lane in a lease, so the loop makes about as many passes as the busiest
    cell has hour ticks, however many price changes a seek waits through.

    Two devices make this exact *and* cheap:

    * ``price_at(t) <= a_bid`` iff ``t`` falls inside an availability period
      of the cell — the same float comparisons ``available_periods`` made on
      the original ``trace.times`` values — and every lane's query stream is
      monotone in ``t`` (seek ticks, then ``t_cd < t_td`` per hour, then the
      relaunch seek), so one forward-only per-lane period cursor answers all
      membership queries in amortized O(1).
    * When every poll tick ``k * poll`` up to the horizon is a float64 value
      exactly (:func:`_poll_lattice_exact`; any whole-second poll), the walk
      only ever stands on those ticks, each step ``t + poll`` lands on the
      next one, and it can skip none: its next stop never passes the first
      tick at or after the next period's start.  So from an opening tick
      outside every period it stops first inside period ``p`` at
      ``TK[c, p]`` and launches at the first period whose tick lands inside
      it — a lookup in :meth:`_PeriodGrid.acc_launch`, with no walk over the
      price changes.  A lane with no such period before the horizon retires,
      as the scalar walk returns ``None`` with no observable state change.

    On a lattice that rounds, ``t + poll`` and ``k * poll`` can part by an
    ulp and the ticks depend on the path, so there each seeking lane runs
    the scalar walk (:func:`repro.core.simulator._poll_walk`) itself.

    Self-terminated lanes re-enter seek from ``terminated_at + _EPS``; a
    lease that runs off the horizon is billed OUT_OF_BID-style over
    ``[launch, horizon)`` with no work_lost charge, mirroring the scalar.
    ACC reports ``n_kills = 0`` (never provider-killed), so the
    kill-counting half of :func:`_bill_runs_flat` is discarded.  Counters
    ``acc.seeks`` (seek episodes resolved: launches plus retirements) and
    ``acc.passes`` (passes of the loop) show the cost stays with the hours.
    """
    params = scenario.params
    work_s = scenario.work_s
    t_r, t_c, t_w = params.t_r, params.t_c, params.t_w
    delta, poll = params.billing_period_s, params.poll_s
    C, P = grid.A.shape
    tel = obs.current()

    done = np.zeros(C, dtype=bool)
    comp_time = np.full(C, np.inf)
    n_ckpt = np.zeros(C, dtype=np.int64)
    n_term = np.zeros(C, dtype=np.int64)
    work_lost = np.zeros(C)
    # flat run records (lease ordinal, cell, launch, end, user) — the ordinal
    # keeps each cell's runs chronological for the billing lexsort
    Rp: list[np.ndarray] = []
    Rc: list[np.ndarray] = []
    Ra: list[np.ndarray] = []
    Re: list[np.ndarray] = []
    Ru: list[np.ndarray] = []

    def record(pv, cv, av, ev, user: bool) -> None:
        Rp.append(pv)
        Rc.append(cv)
        Ra.append(av)
        Re.append(ev)
        Ru.append(np.full(len(cv), user, dtype=bool))

    exact = _poll_lattice_exact(poll, float(grid.horizon.max(initial=0.0)))
    if exact:
        TK, NXT = grid.acc_launch(poll)
    else:
        bid_c = np.concatenate([scenario.market_bids(m) for m in grid.markets])

    idx = np.arange(C)  # global cell ids of the active set
    N = C
    pcnt_a = grid.valid.sum(axis=1)
    hor_a = grid.horizon
    ptr = np.zeros(N, dtype=np.int64)  # per-lane monotone period cursor

    def admissible(mask, tq):
        # price_at(tq) <= a_bid  ⟺  tq inside an availability period; NaN
        # pads compare False, so the cursor stops at the first real period
        # ending after tq (or runs out: ptr == pcnt_a)
        while True:
            pc = np.minimum(ptr, P - 1)
            mv = mask & (ptr < pcnt_a) & (grid.B[idx, pc] <= tq)
            if not mv.any():
                break
            ptr[mv] += 1
        pc = np.minimum(ptr, P - 1)
        return mask & (ptr < pcnt_a) & (grid.A[idx, pc] <= tq) & (tq < grid.B[idx, pc])

    alive = np.ones(N, dtype=bool)
    seeking = np.ones(N, dtype=bool)
    sv = np.full(N, float(scenario.initial_saved_work))
    L = np.zeros(N)
    t = np.zeros(N)
    work = np.zeros(N)
    kk = np.ones(N, dtype=np.int64)  # hour index within the current lease
    ordn = np.zeros(N, dtype=np.int64)
    # each seeking lane's opening poll tick: 0.0 at the start (the scalar
    # launches there when the opening price already admits the bid)
    ts = np.zeros(N)
    n_seeks = n_passes = 0

    while alive.any():
        n_passes += 1
        # -- seek: resolve every seeking lane's launch tick (or retire it)
        seek = alive & seeking
        if seek.any():
            n_seeks += int(seek.sum())
            # launch at the opening tick when it is inside a period (never
            # past the horizon: every period ends by then)
            ok = admissible(seek, ts)
            rows = np.nonzero(seek & ~ok)[0]
            if rows.size:
                if exact:
                    # first period from the cursor whose tick lands inside
                    # it, so TK < B <= horizon; none left: P, retire
                    p_star = NXT[idx[rows], ptr[rows]]
                    tk = TK[idx[rows], np.minimum(p_star, P - 1)]
                    go = p_star < P
                    ptr[rows[go]] = p_star[go]
                else:
                    tk = np.array(
                        [_walk_or_nan(grid, bid_c, idx[r], ts[r], poll) for r in rows]
                    )
                    go = ~np.isnan(tk)
                ts[rows[go]] = tk[go]
                ok[rows[go]] = True
                alive[rows[~go]] = False
            L = np.where(ok, ts, L)
            t = np.where(ok, ts + t_r, t)  # t = L + t_r
            work = np.where(ok, sv, work)
            kk = np.where(ok, 1, kk)
            seeking &= ~ok

        live = alive & ~seeking
        if not live.any():
            continue

        t_h = L + kk * delta
        runoff = live & (t_h > hor_a)
        if runoff.any():
            # lease runs off the horizon: billed OUT_OF_BID over [L, horizon)
            # (full hours charged, partial final hour free), no work_lost
            rb = runoff & (hor_a > L)
            if rb.any():
                record(ordn[rb], idx[rb], L[rb], hor_a[rb], False)
            alive &= ~runoff
            live &= ~runoff
            if not live.any():
                continue

        # Eq. (3)-(4) decision points (schemes.decision_points, inlined)
        t_cd = t_h - t_c - t_w
        t_td = t_h - t_w
        take = live & ~admissible(live, t_cd)
        term_q = live & ~admissible(live, t_td)
        live2, t, work, sv, d_at, fin, ck, term = acc_lease_tick(
            np, live, t_h, take, term_q, t, work, sv, work_s, t_c
        )
        if fin.any():
            rows = idx[fin]
            comp_time[rows] = d_at[fin]
            done[rows] = True
            record(ordn[fin], rows, L[fin], d_at[fin], True)
            alive &= ~fin
        if ck.any():
            n_ckpt[idx[ck]] += 1
        if term.any():
            rows = idx[term]
            record(ordn[term], rows, L[term], t_h[term], True)
            ordn[term] += 1
            n_term[rows] += 1
            work_lost[rows] += work[term] - sv[term]
            seeking |= term  # lane stays alive, back to the poll walk
            # _next_launch_time(terminated_at + _EPS, ...) opening tick
            ts = np.where(term, np.ceil((t_h + _EPS) / poll - _EPS) * poll, ts)
        kk = np.where(live2, kk + 1, kk)

        # -- compact: drop finished cells so the tail runs on small arrays
        na = int(alive.sum())
        if na and na <= N // 2:
            tel.count("acc.compactions")
            keep = alive
            idx, pcnt_a, hor_a = idx[keep], pcnt_a[keep], hor_a[keep]
            ptr, sv, L, t, work = ptr[keep], sv[keep], L[keep], t[keep], work[keep]
            kk, ts, ordn, seeking = kk[keep], ts[keep], ordn[keep], seeking[keep]
            alive = np.ones(na, dtype=bool)
            N = na

    tel.count("acc.seeks", n_seeks)
    tel.count("acc.passes", n_passes)
    with tel.span("bill", scheme=Scheme.ACC.value):
        if Rc:
            total, _ = _bill_runs_flat(
                grid,
                np.concatenate(Rp),
                np.concatenate(Rc),
                np.concatenate(Ra),
                np.concatenate(Re),
                np.concatenate(Ru),
                delta,
            )
        else:
            total = np.zeros(C)

    return {
        "completed": done & np.isfinite(comp_time),
        "completion_time": comp_time,
        "cost": total,
        "n_checkpoints": n_ckpt,
        "n_kills": np.zeros(C, dtype=np.int64),  # ACC is never provider-killed
        "work_lost_s": work_lost,
        "n_self_terminations": n_term,
    }


def _poll_lattice_exact(poll: float, horizon: float) -> bool:
    """Whether every poll tick ``k * poll`` up to two past ``horizon`` is a
    float64 value exactly (``poll = n / 2**s`` with ``k * n < 2**53``), so
    that ``k * poll + poll == (k + 1) * poll`` and the seek's ticks do not
    depend on its path."""
    return Fraction(poll).numerator * (math.ceil(horizon / poll) + 2) < 2**53


def _walk_or_nan(grid: _PeriodGrid, bid_c: np.ndarray, cell: int, tick: float, poll: float):
    """The scalar poll walk of one cell from ``tick`` (NaN for ``None``)."""
    trace = grid.markets[cell // grid.n_bids].trace
    launch = _poll_walk(trace, float(tick), float(bid_c[cell]), poll)
    return np.nan if launch is None else launch


# ---------------------------------------------------------------------------
# Billing — vectorized bill_run with hour-order cost accumulation
# ---------------------------------------------------------------------------


def _bill_runs(
    grid: _PeriodGrid,
    runs: list[tuple[int, np.ndarray, np.ndarray, np.ndarray, bool]],
    delta: float,
) -> tuple[np.ndarray, np.ndarray]:
    """Bill per-period run groups (``(period, cells, launch, end, user)``) —
    flattens and delegates to :func:`_bill_runs_flat`."""
    if not runs:
        C = grid.A.shape[0]
        return np.zeros(C), np.zeros(C, dtype=np.int64)
    sizes = np.asarray([len(r[1]) for r in runs])
    return _bill_runs_flat(
        grid,
        np.repeat([r[0] for r in runs], sizes),
        np.concatenate([r[1] for r in runs]),
        np.concatenate([r[2] for r in runs]),
        np.concatenate([r[3] for r in runs]),
        np.repeat(np.asarray([r[4] for r in runs], dtype=bool), sizes),
        delta,
    )


def _bill_runs_flat(
    grid: _PeriodGrid,
    p_all: np.ndarray,
    cells: np.ndarray,
    launch: np.ndarray,
    end: np.ndarray,
    user: np.ndarray,
    delta: float,
) -> tuple[np.ndarray, np.ndarray]:
    """Bill every recorded run and fold into per-cell totals.

    Runs arrive as flat parallel arrays (one entry per billed instance run,
    in any order — a cell records at most one run per period, which is what
    makes order irrelevant here).  Runs are grouped per market so price
    lookups share one (times, prices) pair; within a run, hour prices
    accumulate in hour order (hour 0, then 1, ...) and across a cell's runs
    costs accumulate in period (= chronological) order, so each cell's total
    is the exact left-to-right sum the scalar ``run_cost`` / ``sum(r.cost for
    r in runs)`` produces.  Also derives ``n_kills`` (non-user-terminated
    recorded runs, exactly the scalar count).  Counts the runs billed in
    ``bill.runs`` and their billing periods in ``bill.hours``.
    """
    C = grid.A.shape[0]
    total = np.zeros(C)
    n_kills = np.zeros(C, dtype=np.int64)
    tel = obs.current()
    tel.count("bill.runs", len(cells))
    if len(cells) == 0:
        return total, n_kills
    m_of = cells // grid.n_bids

    run_cost = np.zeros(len(cells))
    hours = 0
    for m in np.unique(m_of):
        sel = np.nonzero(m_of == m)[0]
        tr = grid.markets[m].trace
        l_m, e_m, u_m = launch[sel], end[sel], user[sel]
        # int(math.ceil((end - launch) / Δ - 1e-12))
        n_hours = np.ceil((e_m - l_m) / delta - 1e-12).astype(np.int64)
        Q = int(n_hours.sum())
        hours += Q
        if Q == 0:
            continue
        # one flat (run, hour) query batch: run-major, hours ascending
        run_of_q = np.repeat(np.arange(len(sel)), n_hours)
        hour_of_q = np.arange(Q) - np.repeat(np.cumsum(n_hours) - n_hours, n_hours)
        start = l_m[run_of_q] + hour_of_q * delta  # launch + k * Δ
        seg = np.searchsorted(tr.times, start, side="right") - 1
        seg = np.clip(seg, 0, len(tr.prices) - 1)
        price = tr.prices[seg]
        full = (start + delta) <= (e_m[run_of_q] + 1e-9)
        charged = full | u_m[run_of_q]
        rc = np.zeros(len(sel))
        # np.add.at accumulates sequentially in query order = hour order,
        # reproducing the scalar's left-to-right per-run price sum exactly
        np.add.at(rc, run_of_q[charged], price[charged])
        run_cost[sel] = rc
    tel.count("bill.hours", hours)

    np.add.at(n_kills, cells[~user], 1)
    # a cell records at most one run per period, so sorting runs by (cell,
    # period) and letting np.add.at accumulate sequentially in that order
    # reproduces each cell's chronological left-to-right cost sum exactly
    # (run costs are >= 0.0, so dropping the old scatter's x + 0.0 adds for
    # run-less periods changes no bit) — one segment op, no per-period loop
    order = np.lexsort((p_all, cells))
    np.add.at(total, cells[order], run_cost[order])
    return total, n_kills
