"""Pure scheme kernels: the per-period lockstep math, backend-agnostic.

Every bid-limited scheme (NONE / OPT / HOUR / EDGE / ADAPT) is expressed here
as a pure function over arrays — no engine state, no trace objects, no I/O.
Each kernel takes its array namespace ``xp`` as the first argument, so the
same expressions run on NumPy (:class:`~repro.engine.batch.BatchEngine`) and
on ``jax.numpy`` (:class:`~repro.engine.jax_backend.JaxEngine` feeds the
shared single-step bodies into ``lax.while_loop``).

Exactness is the design contract: every floating-point expression mirrors the
scalar reference (:mod:`repro.core.simulator`) in both formula *and*
association order — ``work + (s - t)``, ``t + (work_s - work)`` — so IEEE-754
evaluation is bit-identical and :mod:`repro.engine.parity` can assert ``==``
rather than ``allclose``.  ``_EPS`` is imported from the scalar simulator (one
constant, not a copy-pasted contract).  When editing simulation semantics,
change :mod:`repro.core.simulator` first, then mirror here.

ADAPT is lowered through *binned hazard tables*: the per-step "checkpoint
now?" decision only reads the failure pdf through its binned survival
function, so :class:`AdaptTables` packs each (market, bid) cell's
:meth:`~repro.core.schemes.FailurePdf.compact_survival` table once and the
per-tick decision becomes two table gathers plus the Yi et al. comparison
``hazard * (unsaved + t_r) > t_c`` — advancing in lockstep like every other
scheme instead of falling back to the scalar loop.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.schemes import FailurePdf, Scheme
from repro.core.simulator import _EPS

__all__ = [
    "AdaptTables",
    "_EPS",
    "_kernel_adapt",
    "_kernel_none",
    "_kernel_opt",
    "_kernel_windows",
    "acc_lease_tick",
    "adapt_decision",
    "adapt_tick",
    "adapt_tick_core",
    "period_step_masked",
    "windows_advance",
]


# ---------------------------------------------------------------------------
# Stateless elementwise kernels
# ---------------------------------------------------------------------------


def _kernel_none(xp, b, start_work, saved, work_s):
    """NONE: no checkpoint windows; one straight work segment per period."""
    lhs = saved + (b - start_work)  # work + (b - t)
    done_now = lhs >= (work_s - _EPS)
    done_at = start_work + (work_s - saved)  # t + (work_s - work)
    return (
        done_now,
        done_at,
        lhs,
        saved,
        xp.zeros(b.shape[0], dtype=xp.int64),
    )


def _kernel_opt(xp, b, start_work, saved, work_s, t_c):
    """OPT oracle: checkpoint exactly once, just before the kill — iff the
    kill precedes completion."""
    remaining = work_s - saved
    completes_at = start_work + remaining
    oracle = completes_at <= (b + _EPS)
    s = b - t_c
    has_s = (~oracle) & (s > start_work)

    # no-window path (oracle completion or window before recovery finished)
    lhsB = saved + (b - start_work)
    doneB = lhsB >= (work_s - _EPS)
    done_atB = start_work + (work_s - saved)

    # window path
    w_at_s = saved + (s - start_work)  # work + (s - t)
    doneA1 = w_at_s >= (work_s - _EPS)
    done_atA1 = start_work + (work_s - saved)
    ckpt_ok = (s + t_c) <= (b + _EPS)
    work1 = w_at_s
    saved1 = xp.where(ckpt_ok, work1, saved)
    t1 = s + t_c
    ended = t1 >= b
    lhsA2 = work1 + (b - t1)
    doneA2 = (~ended) & (lhsA2 >= (work_s - _EPS))
    done_atA2 = t1 + (work_s - work1)
    work_endA = xp.where(ended, work1, lhsA2)

    done_now = xp.where(has_s, doneA1 | doneA2, doneB)
    done_at = xp.where(has_s, xp.where(doneA1, done_atA1, done_atA2), done_atB)
    work_end = xp.where(has_s, work_endA, lhsB)
    saved_out = xp.where(has_s & ~doneA1, saved1, saved)
    ckpt_add = (has_s & ~doneA1 & ckpt_ok).astype(xp.int64)
    return done_now, done_at, work_end, saved_out, ckpt_add


def period_step_masked(xp, scheme, state, a, b, valid, horizon, t_r, run_kernel):
    """One padded-period lockstep advance with *masks* in place of the NumPy
    driver's index compression (masked lanes cost nothing under vmap-style
    array execution, and compression would make traced shapes dynamic).

    The per-period orchestration of the fused sweep program (the jitted
    ``lax.scan`` in :mod:`repro.kernels.spot_sweep`): enter the period,
    consume too-short availability windows, dispatch the scheme kernel via
    ``run_kernel(go, a, b, start_work, saved)``, then fold completions /
    kills / checkpoint counts into the carried state.  ``state`` is the 7-tuple ``(saved, done,
    comp_time, n_ckpt, work_lost, has_run, n_kills)`` — ``n_kills``
    accumulates on-device (one count per non-user-terminated recorded run,
    exactly the billing-side tally).  Returns ``(state, (rec_exists, rec_end,
    rec_user))`` where the records feed the vectorized biller.

    Float expressions mirror :mod:`repro.engine.batch._run_scheme` line for
    line, so results are bit-identical to the NumPy driver.
    """
    saved, done, comp_time, n_ckpt, work_lost, has_run, n_kills = state
    none_reset = scheme == Scheme.NONE
    act = valid & ~done
    start_work = a + t_r
    if none_reset:
        # NONE restarts from scratch after any recorded run
        saved = xp.where(act & has_run, 0.0, saved)

    short = act & (start_work >= b)
    shortk = short & (b < horizon)
    go = act & ~short

    done_now, done_at, work_end, saved_out, ckpt_add = run_kernel(go, a, b, start_work, saved)
    done_now = go & done_now

    n_ckpt = n_ckpt + xp.where(go, ckpt_add, 0)
    comp_time = xp.where(done_now, done_at, comp_time)
    done = done | done_now
    kl = go & ~done_now
    if none_reset:
        work_lost = xp.where(kl, work_lost + (work_end - 0.0), work_lost)
        has_run = has_run | shortk | kl
    else:
        work_lost = xp.where(kl, work_lost + (work_end - saved_out), work_lost)
        saved = xp.where(kl, saved_out, saved)
    n_kills = n_kills + (shortk | kl).astype(n_kills.dtype)

    rec_exists = shortk | done_now | kl
    rec_end = xp.where(done_now, done_at, b)
    state = (saved, done, comp_time, n_ckpt, work_lost, has_run, n_kills)
    return state, (rec_exists, rec_end, done_now)


# ---------------------------------------------------------------------------
# HOUR / EDGE: scheduled checkpoint windows, one lockstep iteration at a time
# ---------------------------------------------------------------------------


def windows_advance(xp, s, window, state, work_s, t_c, b):
    """Apply one checkpoint window starting at ``s`` to every ``window`` cell.

    ``state = (work, t, sv, done_now, done_at, ckpt_add, in_loop)``; returns
    the updated state.  Shared single-step body of the HOUR/EDGE walk — the
    NumPy driver calls it in a host loop, the JAX driver inside
    ``lax.while_loop``.
    """
    work, t, sv, done_now, done_at, ckpt_add, in_loop = state
    w_at = work + (s - t)
    d = window & (w_at >= (work_s - _EPS))
    done_now = done_now | d
    done_at = xp.where(d, t + (work_s - work), done_at)
    in_loop = in_loop & ~d
    window = window & ~d

    work = xp.where(window, w_at, work)
    ckpt_ok = window & ((s + t_c) <= (b + _EPS))
    sv = xp.where(ckpt_ok, work, sv)
    ckpt_add = ckpt_add + ckpt_ok.astype(xp.int64)
    t = xp.where(window, s + t_c, t)
    billed_out = window & (t >= b)
    in_loop = in_loop & ~billed_out
    return window, (work, t, sv, done_now, done_at, ckpt_add, in_loop)


def _kernel_windows(
    xp,
    a,
    b,
    start_work,
    saved,
    work_s,
    t_c,
    hour_delta: float | None = None,
    edge_state: tuple | None = None,
):
    """HOUR / EDGE: walk scheduled checkpoint windows in lockstep.

    The loop advances one window index per iteration for every active cell
    simultaneously; a cell drops out when it completes, is billed out at
    ``t >= b``, or runs out of windows (tail segment).  Window start times
    come from hour boundaries (``hour_delta``) or the trace's rising edges
    (``edge_state`` = per-cell views into the flattened edge arrays).

    The walk compacts its working set whenever fewer than half the remaining
    rows are still in the loop (a handful of long-availability cells drive
    the iteration tail), scattering results back to full width at the end —
    a pure scheduling change, so results stay bit-identical.  The compaction
    scatter buffers are host NumPy (this driver loop is host-side by nature;
    the jitted JAX driver builds on :func:`windows_advance` directly).
    """
    C = b.shape[0]
    b_full = b
    work_s_full = work_s  # per-lane work_s must survive compaction (fleet lanes)
    rows = np.arange(C)  # current → original row mapping (host-side)
    work = saved
    t = start_work
    sv = saved
    done_now = xp.zeros(C, dtype=bool)
    done_at = xp.full(C, np.nan)
    ckpt_add = xp.zeros(C, dtype=xp.int64)
    tail = xp.zeros(C, dtype=bool)
    in_loop = xp.ones(C, dtype=bool)
    if edge_state is not None:
        edges_flat, base, n_edges, ptr = edge_state
    # full-width result buffers (written back on compaction / exit)
    out = {
        "work": np.zeros(C), "t": np.zeros(C), "sv": np.zeros(C),
        "done_now": np.zeros(C, dtype=bool), "done_at": np.full(C, np.nan),
        "ckpt_add": np.zeros(C, dtype=np.int64), "tail": np.zeros(C, dtype=bool),
    }

    def flush():
        out["work"][rows] = np.asarray(work)
        out["t"][rows] = np.asarray(t)
        out["sv"][rows] = np.asarray(sv)
        out["done_now"][rows] = np.asarray(done_now)
        out["done_at"][rows] = np.asarray(done_at)
        out["ckpt_add"][rows] = np.asarray(ckpt_add)
        out["tail"][rows] = np.asarray(tail)

    k = 1
    while bool(xp.any(in_loop)):
        if edge_state is None:
            s = a + k * hour_delta - t_c  # launch + k*Δ - t_c
            no_more = in_loop & ~(s < b)
            window = in_loop & (s < b) & (s > start_work)
            # s <= start_work windows are skipped but the walk continues
        else:
            have = in_loop & (ptr < n_edges)
            idx = xp.where(have, base + ptr, 0)
            s = xp.where(have, edges_flat[idx], np.inf)
            no_more = in_loop & (~have | ~(s < b))
            window = in_loop & have & (s < b)
        tail = tail | no_more
        in_loop = in_loop & ~no_more

        state = (work, t, sv, done_now, done_at, ckpt_add, in_loop)
        window, state = windows_advance(xp, s, window, state, work_s, t_c, b)
        work, t, sv, done_now, done_at, ckpt_add, in_loop = state
        if edge_state is not None:
            ptr = ptr + window  # only consumed edges advance
        k += 1

        live = int(in_loop.sum())
        if live and live <= len(rows) // 2:
            flush()
            keep = np.asarray(in_loop)
            rows = rows[keep]
            a, b, start_work = a[keep], b[keep], start_work[keep]
            work, t, sv = work[keep], t[keep], sv[keep]
            done_now, done_at, ckpt_add = done_now[keep], done_at[keep], ckpt_add[keep]
            tail = tail[keep]
            in_loop = in_loop[keep]
            if np.ndim(work_s):
                work_s = work_s[keep]
            if edge_state is not None:
                base, n_edges, ptr = base[keep], n_edges[keep], ptr[keep]

    flush()
    work, t, sv = out["work"], out["t"], out["sv"]
    done_now, done_at, ckpt_add, tail = (
        out["done_now"], out["done_at"], out["ckpt_add"], out["tail"],
    )
    b = b_full
    work_s = work_s_full

    # tail segment: work to b, maybe completing
    lhs = work + (b - t)
    d2 = tail & (lhs >= (work_s - _EPS))
    done_now = done_now | d2
    done_at = xp.where(d2, t + (work_s - work), done_at)
    work_end = xp.where(tail, lhs, work)
    return done_now, done_at, work_end, sv, ckpt_add


# ---------------------------------------------------------------------------
# ADAPT: binned-hazard decision table, walked at the decision cadence
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AdaptTables:
    """Per-cell binned survival tables for the lockstep ADAPT kernel.

    One :meth:`~repro.core.schemes.FailurePdf.compact_survival` table per
    (market, bid) cell, concatenated into ``flat`` with per-cell ``off``-sets
    and plateau indices ``top`` (cells are market-major, matching the
    ``_PeriodGrid`` cell axis).  ``lookup`` reads survival at an integer age
    bin: index ``min(k, top)`` inside the observed failure range, the plateau
    at ``top`` up to ``n_bins``, and the censored tail entry (``top + 1``)
    past it — the exact floats :meth:`FailurePdf.survival` returns, so the
    batched hazard decision is the same bit pattern as the scalar one.
    """

    flat: np.ndarray  # float64, concatenated compact tables
    off: np.ndarray  # (C,) int64 start of each cell's table
    top: np.ndarray  # (C,) int64 plateau index within each table
    bin_s: float
    n_bins: int  # K: ages binned at >= K read the censored entry

    @staticmethod
    def build(markets, scenario, grid=None) -> "AdaptTables":
        """Materialize the decision tables for every (market, bid) cell of a
        scenario.

        Without ``grid``, each cell's pdf is built by the exact scalar path
        (:meth:`FailurePdf.from_trace` + :meth:`~FailurePdf.compact_survival`).
        With a :class:`~repro.engine.batch._PeriodGrid`, the same numbers are
        produced vectorized per market — the grid's padded ``(cell, period)``
        arrays already hold every availability interval, so binning, the
        ``1/n`` mass accumulation (``np.add.at`` in the scalar's chronological
        order) and the cumulative-sum survival rows all run as array ops.
        Both paths are bit-identical (asserted by the engine test suite).
        """
        if grid is not None:
            return _build_tables_from_grid(markets, grid)
        vals: list[np.ndarray] = []
        offs: list[int] = []
        tops: list[int] = []
        pos = 0
        bin_s: float | None = None
        n_bins: int | None = None
        for cellm in markets:
            for bid in scenario.market_bids(cellm):
                pdf = FailurePdf.from_trace(cellm.trace, bid)
                v, tp = pdf.compact_survival()
                if bin_s is None:
                    bin_s, n_bins = pdf.bin_s, len(pdf.pdf)
                elif bin_s != pdf.bin_s or n_bins != len(pdf.pdf):  # pragma: no cover
                    raise ValueError("ADAPT cells must share bin_s / max_bins")
                offs.append(pos)
                tops.append(tp)
                vals.append(v)
                pos += len(v)
        return AdaptTables(
            flat=np.concatenate(vals) if vals else np.zeros(1),
            off=np.asarray(offs, dtype=np.int64),
            top=np.asarray(tops, dtype=np.int64),
            bin_s=float(bin_s if bin_s is not None else FailurePdf.DEFAULT_BIN_S),
            n_bins=int(n_bins if n_bins is not None else 1),
        )


def _build_tables_from_grid(markets, grid) -> AdaptTables:
    """Vectorized :meth:`AdaptTables.build`: survival tables straight from the
    period grid, one batch of array ops per market.

    Mirrors :meth:`FailurePdf.from_trace` float-for-float: failure durations
    are ``B - A`` of the non-censored periods (the grid reads both from
    ``trace.times`` exactly as ``available_periods`` does), each contributes
    ``1.0 / n`` in chronological order, and the survival rows are
    ``1 - cumsum`` — the same sequential sums the scalar tables cache.
    """
    bin_s = FailurePdf.DEFAULT_BIN_S
    K = FailurePdf.DEFAULT_MAX_BINS
    vals: list[np.ndarray] = []
    tops_all: list[np.ndarray] = []
    lens_all: list[np.ndarray] = []
    for m, sl in grid.market_slices():
        A, B, V = grid.A[sl], grid.B[sl], grid.valid[sl]
        nb = A.shape[0]
        horizon = markets[m].trace.horizon
        killed = V & (B < horizon)
        n = V.sum(axis=1)  # durations + censored, as the scalar counts
        cens_n = n - killed.sum(axis=1)
        rows, cols = np.nonzero(killed)  # row-major = chronological per cell
        k = np.minimum(((B[rows, cols] - A[rows, cols]) / bin_s).astype(np.int64), K - 1)
        Ka = int(k.max()) + 2 if k.size else 1
        pdf = np.zeros((nb, Ka))
        w = np.where(n > 0, 1.0 / np.maximum(n, 1), 0.0)
        np.add.at(pdf, (rows, k), w[rows])  # sequential adds in scalar order
        # last occupied bin per row (mass at k implies pdf[k] != 0: the adds
        # are positive), so the survival plateau starts at L + 1
        L = np.full(nb, -1, dtype=np.int64)
        np.maximum.at(L, rows, k)
        top = np.minimum(L + 1, K - 1)
        cum = np.cumsum(pdf[:, : max(int(top.max()), 1)], axis=1)
        censored = np.where(n > 0, cens_n / np.maximum(n, 1), 1.0)
        # ragged-flatten [1, 1 - cum[:top]] + [censored] per row, no Python loop
        top1 = top + 1
        off_local = np.cumsum(top + 2) - (top + 2)
        rowrep = np.repeat(np.arange(nb), top1)
        pos = np.arange(int(top1.sum())) - np.repeat(np.cumsum(top1) - top1, top1)
        flat_m = np.empty(int((top + 2).sum()))
        flat_m[off_local[rowrep] + pos] = np.where(
            pos == 0, 1.0, 1.0 - cum[rowrep, np.maximum(pos - 1, 0)]
        )
        flat_m[off_local + top1] = censored
        vals.append(flat_m)
        tops_all.append(top)
        lens_all.append(top + 2)
    lens = np.concatenate(lens_all)
    return AdaptTables(
        flat=np.concatenate(vals) if vals else np.zeros(1),
        off=np.concatenate(([0], np.cumsum(lens)[:-1])).astype(np.int64),
        top=np.concatenate(tops_all).astype(np.int64),
        bin_s=float(bin_s),
        n_bins=int(K),
    )


def _survival_at(xp, k, flat, off, top, n_bins):
    """Gather binned survival for integer age bins ``k`` (per-cell tables)."""
    idx = xp.where(k >= n_bins, top + 1, xp.minimum(k, top))
    return flat[off + idx]


def adapt_decision(xp, age, unsaved, flat, off, top, bin_s, n_bins, t_c, t_r, interval):
    """Yi et al.'s ADAPT rule as an elementwise table lookup.

    Mirrors :func:`repro.core.schemes.adapt_should_checkpoint` +
    :meth:`FailurePdf.hazard` exactly: survival now and one decision window
    ahead, hazard ``clip((s_now - s_later) / s_now, 0, 1)`` (1 when the
    survival mass is exhausted), checkpoint iff ``h * (unsaved + t_r) > t_c``.
    """
    k1 = (age / bin_s).astype(xp.int64)
    s_now = _survival_at(xp, k1, flat, off, top, n_bins)
    k2 = ((age + interval) / bin_s).astype(xp.int64)
    s_later = _survival_at(xp, k2, flat, off, top, n_bins)
    dead = s_now <= 0.0
    den = xp.where(dead, 1.0, s_now)
    h = xp.where(dead, 1.0, xp.clip((s_now - s_later) / den, 0.0, 1.0))
    return (h * (unsaved + t_r)) > t_c


def adapt_tick_core(
    xp, live, t, work, sv, next_dec, a, b, work_s, t_c, t_r, interval,
    flat, off, top, bin_s, n_bins,
):
    """One ADAPT decision tick, the single shared body.

    Mirrors one iteration of the scalar decision loop in
    ``repro.core.simulator._run_period``: work to the next decision point (or
    the kill), maybe complete, then decide via the binned hazard whether to
    spend ``t_c`` checkpointing before the next interval.  Every ADAPT driver
    calls this one function — :func:`adapt_tick` (the period-synchronized
    walk), the NumPy cell-decoupled driver (``batch._run_adapt``) and its
    traced twin (``spot_sweep.kernel._adapt_decoupled``) — so a semantics
    change is mirrored from the scalar simulator exactly once.

    Returns ``(live, t, work, sv, next_dec, d_at, fin, ck, kl)``: the
    advanced clocks, the would-be completion time ``d_at`` (valid on ``fin``
    lanes), and the completion / checkpoint-taken / killed masks for the
    caller's own bookkeeping (records, counters, compaction).
    """
    seg_end = xp.minimum(next_dec, b)
    fin = live & (work + (seg_end - t) >= work_s - _EPS)
    d_at = t + (work_s - work)
    live = live & ~fin
    work = xp.where(live, work + (seg_end - t), work)
    t = xp.where(live, seg_end, t)
    kill1 = live & (t >= b)  # killed at b with no decision left
    live = live & ~kill1

    age = t - a
    take = live & adapt_decision(
        xp, age, work - sv, flat, off, top, bin_s, n_bins, t_c, t_r, interval
    )
    ck = take & ((t + t_c) <= (b + _EPS))
    sv = xp.where(ck, work, sv)
    t = xp.where(take, xp.minimum(t + t_c, b), t)
    kill2 = take & (t >= b)
    live = live & ~kill2
    next_dec = xp.where(live, t + interval, next_dec)
    return live, t, work, sv, next_dec, d_at, fin, ck, kill1 | kill2


def adapt_tick(xp, state, a, b, work_s, t_c, t_r, interval, flat, off, top, bin_s, n_bins):
    """One period-synchronized ADAPT tick for every in-loop cell.

    ``state = (in_loop, t, work, sv, next_dec, done_now, done_at, ckpt_add)``.
    A thin bookkeeping wrapper over :func:`adapt_tick_core` for the
    ``_kernel_adapt`` host loop.
    """
    in_loop, t, work, sv, next_dec, done_now, done_at, ckpt_add = state
    live, t, work, sv, next_dec, d_at, fin, ck, _ = adapt_tick_core(
        xp, in_loop, t, work, sv, next_dec, a, b, work_s, t_c, t_r, interval,
        flat, off, top, bin_s, n_bins,
    )
    done_now = done_now | fin
    done_at = xp.where(fin, d_at, done_at)
    ckpt_add = ckpt_add + ck.astype(xp.int64)
    return live, t, work, sv, next_dec, done_now, done_at, ckpt_add


def _kernel_adapt(xp, a, b, start_work, saved, work_s, t_c, t_r, interval, tables, cells):
    """ADAPT: walk the decision cadence in lockstep, hazards from binned
    tables.

    ``tables`` is an :class:`AdaptTables`; ``cells`` selects each row's
    (market, bid) table (global cell indices on the grid's flattened cell
    axis).  Returns the same ``(done_now, done_at, work_end, saved_out,
    ckpt_add)`` tuple as every other kernel.
    """
    C = b.shape[0]
    off = tables.off[cells]
    top = tables.top[cells]
    flat = tables.flat
    state = (
        xp.ones(C, dtype=bool),  # in_loop
        start_work,  # t
        saved,  # work
        saved,  # sv
        start_work + interval,  # next_dec
        xp.zeros(C, dtype=bool),  # done_now
        xp.full(C, np.nan),  # done_at
        xp.zeros(C, dtype=xp.int64),  # ckpt_add
    )
    while bool(xp.any(state[0])):
        state = adapt_tick(
            xp, state, a, b, work_s, t_c, t_r, interval,
            flat, off, top, tables.bin_s, tables.n_bins,
        )
    _, _, work, sv, _, done_now, done_at, ckpt_add = state
    return done_now, done_at, work, sv, ckpt_add


def acc_lease_tick(xp, live, t_h, take_ckpt, term_q, t, work, sv, work_s, t_c):
    """One ACC hour-boundary step for every in-lease lane.

    The leased-work variant of :func:`windows_advance`: mirrors one iteration
    of the ``while True`` loop in ``repro.core.simulator._acc_lease``, with
    the two price queries hoisted to the caller — ``take_ckpt`` is
    ``price_at(t_h - t_c - t_w) > a_bid`` and ``term_q`` is
    ``price_at(t_h - t_w) > a_bid`` (Eq. 4 decision points).  The caller
    owns the hour cadence (``t_h = launch + k * billing_period``) and the
    horizon-runoff break, which happen *before* this tick.

    Order matters and is the scalar's, expression for expression: the
    checkpoint-shortened segment end, the completion test (association
    ``work + (seg_end - t)`` and ``t + (work_s - work)``), the
    *unconditional* ``t = seg_end`` for lanes that neither finished nor
    advanced, then checkpoint commit (``sv = work``, ``t = t_h``), then the
    self-termination query.

    Returns ``(live, t, work, sv, d_at, fin, ck, term)``: surviving lanes,
    advanced clocks, the would-be completion time ``d_at`` (valid on ``fin``
    lanes), and the completion / checkpoint-taken / self-terminated masks
    (terminated lanes stop at ``t_h``).
    """
    seg_end = xp.where(take_ckpt, t_h - t_c, t_h)
    adv = live & (seg_end > t)
    fin = adv & (work + (seg_end - t) >= work_s - _EPS)
    d_at = t + (work_s - work)
    live = live & ~fin
    adv = adv & ~fin
    work = xp.where(adv, work + (seg_end - t), work)
    t = xp.where(live, seg_end, t)
    ck = live & take_ckpt
    sv = xp.where(ck, work, sv)
    t = xp.where(ck, t_h, t)
    term = live & term_q
    live = live & ~term
    return live, t, work, sv, d_at, fin, ck, term
