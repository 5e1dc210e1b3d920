"""Pallas fused lockstep sweep: the (type × bid × seed) grid as one program.

The paper's §VII study is a dense sweep — every (instance type, bid, scheme,
seed) cell simulated over a 30-day horizon — and its lockstep form is a scan
over the padded *period* axis with per-period checkpoint-window / decision
walks inside.  This module holds both traced realizations of that sweep:

  * :func:`build_sweep_scan` — the one-compile multi-scheme ``lax.scan``
    program.  Scheme is a *static segment axis* of the trace: every scheme's
    state tuple advances inside the same ``period_step``, so a 5-scheme
    scenario compiles (and dispatches) once instead of five times.
  * :func:`sweep_pallas` — the same step as a fused Pallas TPU kernel:
    grid ``(cell_blocks, periods)`` with the period axis innermost
    (sequential on TPU), the per-scheme state carried in VMEM scratch across
    periods, and the per-period run records streamed to the output blocks.
    It runs in interpret mode only (:data:`NATIVE_UNSUPPORTED` says why).

Both build on the shared per-period orchestration
(:func:`repro.engine.kernels.period_step_masked`) and the shared pure scheme
kernels, so with x64 enabled the results are bit-identical to the NumPy
driver in :mod:`repro.engine.batch` — the triad's ``ref`` — and to the scalar
reference (asserted ``==`` by :mod:`repro.engine.parity`).  Float64 is the
parity substrate: XLA emulates it on the TPU for the scan program, but not
inside a Mosaic kernel.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.schemes import Scheme
from repro.engine import kernels as _k
from repro.engine.kernels import _EPS, period_step_masked

#: Why :func:`sweep_pallas` runs interpreted only: a native compile for the
#: TPU fails as a described-v5e compile reports it (this kernel needs a port off 64-bit
#: types; the scan program compiles, with float64 emulated by XLA).
NATIVE_UNSUPPORTED = (
    "the Pallas sweep kernel does not compile natively for the TPU: its "
    "(cells, 1) blocks are off the (8, 128) tiling Mosaic requires, and XLA's "
    "TPU x64 rewrite has no rule for float64/int64 operands of a Mosaic "
    "kernel (UNIMPLEMENTED for tpu_custom_call); run it with interpret=True "
    "or use engine='jax'"
)

#: Carried per-scheme state, in order (see ``period_step_masked``).
STATE_FIELDS = ("saved", "done", "comp_time", "n_ckpt", "work_lost", "has_run", "n_kills")


def init_state(C: int, init_saved):
    """Fresh state 7-tuple for ``C`` lockstep cells."""
    return (
        jnp.full(C, init_saved, dtype=jnp.float64),  # saved
        jnp.zeros(C, dtype=bool),  # done
        jnp.full(C, np.inf),  # comp_time
        jnp.zeros(C, dtype=jnp.int64),  # n_ckpt
        jnp.zeros(C),  # work_lost
        jnp.zeros(C, dtype=bool),  # has_run (NONE)
        jnp.zeros(C, dtype=jnp.int64),  # n_kills
    )


# ---------------------------------------------------------------------------
# Traced per-period scheme bodies (lax.while_loop over windows / ticks)
# ---------------------------------------------------------------------------


def _windows_kernel(go, a, b, start_work, saved, work_s, t_c, hour_args, edge_args):
    """HOUR / EDGE checkpoint-window walk under ``lax.while_loop``; the traced
    twin of :func:`repro.engine.kernels._kernel_windows` (masks instead of
    host-side compaction), built on the shared ``windows_advance`` step."""
    C = b.shape[0]
    done_at0 = jnp.full(C, np.nan)
    ckpt0 = jnp.zeros(C, dtype=jnp.int64)
    false = jnp.zeros(C, dtype=bool)
    if edge_args is None:
        (hour_delta,) = hour_args
        cursor0 = jnp.asarray(1, dtype=jnp.int64)  # window index k
    else:
        edges_flat, base, n_edges, ptr0 = edge_args
        cursor0 = ptr0

    def cond(st):
        return jnp.any(st[0][6])  # state.in_loop

    def body(st):
        (work, t, sv, done_now, done_at, ckpt_add, in_loop), tail, cursor = st
        if edge_args is None:
            s = a + cursor * hour_delta - t_c
            no_more = in_loop & ~(s < b)
            window = in_loop & (s < b) & (s > start_work)
            # s <= start_work windows are skipped but the walk continues
        else:
            have = in_loop & (cursor < n_edges)
            idx = jnp.where(have, base + cursor, 0)
            s = jnp.where(have, edges_flat[idx], np.inf)
            no_more = in_loop & (~have | ~(s < b))
            window = in_loop & have & (s < b)
        tail = tail | no_more
        in_loop = in_loop & ~no_more
        state = (work, t, sv, done_now, done_at, ckpt_add, in_loop)
        window, state = _k.windows_advance(jnp, s, window, state, work_s, t_c, b)
        cursor = cursor + 1 if edge_args is None else cursor + window
        return state, tail, cursor

    init = ((saved, start_work, saved, false, done_at0, ckpt0, go), false, cursor0)
    (work, t, sv, done_now, done_at, ckpt_add, _), tail, _ = lax.while_loop(cond, body, init)
    # tail segment: work to b, maybe completing
    lhs = work + (b - t)
    d2 = tail & (lhs >= (work_s - _EPS))
    done_now = done_now | d2
    done_at = jnp.where(d2, t + (work_s - work), done_at)
    work_end = jnp.where(tail, lhs, work)
    return done_now, done_at, work_end, sv, ckpt_add


def _adapt_kernel(go, a, b, start_work, saved, work_s, t_c, t_r, adapt_args):
    """ADAPT decision cadence under ``lax.while_loop`` on the shared
    ``adapt_tick`` body (binned-hazard table gathers)."""
    interval, flat, off, top, bin_s, n_bins = adapt_args
    C = b.shape[0]
    init = (
        go,  # in_loop
        start_work,  # t
        saved,  # work
        saved,  # sv
        start_work + interval,  # next_dec
        jnp.zeros(C, dtype=bool),  # done_now
        jnp.full(C, np.nan),  # done_at
        jnp.zeros(C, dtype=jnp.int64),  # ckpt_add
    )

    def cond(state):
        return jnp.any(state[0])

    def body(state):
        return _k.adapt_tick(
            jnp, state, a, b, work_s, t_c, t_r, interval,
            flat, off, top, bin_s, n_bins,
        )

    _, _, work, sv, _, done_now, done_at, ckpt_add = lax.while_loop(cond, body, init)
    return done_now, done_at, work, sv, ckpt_add


def scheme_period_step(scheme: Scheme, state, a, b, valid, horizon, ptr0, c):
    """Advance one scheme's state tuple through one padded period.

    ``c`` maps the scalar simulation constants (``work_s``, ``t_c``, ``t_r``,
    ``hour_delta``, ``interval``, ``bin_s``, ``n_bins`` — traced scalars in
    the scan program, Python floats in the Pallas kernel) and the flat aux
    arrays (``edges_flat``/``edge_base``/``edge_n`` for EDGE,
    ``tab_flat``/``tab_off``/``tab_top`` for ADAPT).  ``ptr0`` is the
    per-cell first-edge cursor for this period (EDGE only).
    """
    work_s, t_c, t_r = c["work_s"], c["t_c"], c["t_r"]
    if scheme == Scheme.NONE:
        def run_kernel(go, a_, b_, sw, sv):
            return _k._kernel_none(jnp, b_, sw, sv, work_s)
    elif scheme == Scheme.OPT:
        def run_kernel(go, a_, b_, sw, sv):
            return _k._kernel_opt(jnp, b_, sw, sv, work_s, t_c)
    elif scheme == Scheme.HOUR:
        def run_kernel(go, a_, b_, sw, sv):
            return _windows_kernel(go, a_, b_, sw, sv, work_s, t_c, (c["hour_delta"],), None)
    elif scheme == Scheme.EDGE:
        def run_kernel(go, a_, b_, sw, sv):
            return _windows_kernel(
                go, a_, b_, sw, sv, work_s, t_c, None,
                (c["edges_flat"], c["edge_base"], c["edge_n"], ptr0),
            )
    elif scheme == Scheme.ADAPT:
        def run_kernel(go, a_, b_, sw, sv):
            return _adapt_kernel(
                go, a_, b_, sw, sv, work_s, t_c, t_r,
                (c["interval"], c["tab_flat"], c["tab_off"], c["tab_top"],
                 c["bin_s"], c["n_bins"]),
            )
    else:  # pragma: no cover - guarded by BATCHED_SCHEMES
        raise ValueError(f"no sweep kernel for {scheme}")
    return period_step_masked(jnp, scheme, state, a, b, valid, horizon, t_r, run_kernel)


# ---------------------------------------------------------------------------
# ADAPT, cell-decoupled: every cell walks its own (period, tick) cursor
# ---------------------------------------------------------------------------


def _adapt_decoupled(A, B, valid, horizon, init_saved, work_s, t_c, t_r,
                     interval, tab_flat, tab_off, tab_top, bin_s, n_bins):
    """The traced twin of :func:`repro.engine.batch._run_adapt`.

    One ``lax.while_loop`` advances every ADAPT cell through its *own*
    ``(period, decision-tick)`` cursor — period entry (consuming too-short
    availability windows) is folded into the loop as a masked phase, so the
    iteration count is the busiest single cell's tick total rather than the
    per-period maximum summed over the padded period axis (~5-10x fewer
    iterations than the period-synchronized walk; this is what makes the jax
    backend beat the NumPy driver).  Per-tick expressions are
    :func:`repro.engine.kernels.adapt_decision` and the same masked updates
    as the NumPy driver, so results stay bit-identical.

    The loop carries *only* ``(C,)`` vectors — no record buffers, no
    scatters.  The billed run records are reconstructed vectorized after the
    loop: every processed period of a cell ends in exactly one record
    (mid-trace shorts and kills end at the period boundary ``B[c, p]``;
    shorts at the horizon are unbilled; the one possible completion ends at
    ``comp_time[c]`` in the cell's final cursor period), so ``(rec_exists,
    rec_end, rec_user)`` are pure functions of the grid plus the final
    ``(p, done, comp_time)`` state.

    Returns ``(state, (rec_exists, rec_end, rec_user))`` with the state
    7-tuple of :func:`init_state` and records shaped ``(P, C)``.
    """
    C, P = A.shape
    rows = jnp.arange(C)
    cnt = valid.sum(axis=1)
    zf = jnp.zeros(C)
    state0 = (
        jnp.full(C, init_saved, dtype=jnp.float64),  # saved
        cnt > 0,  # alive
        jnp.ones(C, dtype=bool),  # entering
        jnp.zeros(C, dtype=jnp.int64),  # p
        zf, zf, zf, zf, zf, zf,  # t, work, sv, next_dec, a_cur, b_cur
        jnp.zeros(C, dtype=bool),  # done
        jnp.full(C, np.inf),  # comp_time
        jnp.zeros(C, dtype=jnp.int64),  # n_ckpt
        zf,  # work_lost
        jnp.zeros(C, dtype=jnp.int64),  # n_kills
    )

    def cond(st):
        return jnp.any(st[1])  # alive

    def body(st):
        (saved, alive, entering, p, t, work, sv, next_dec, a_cur, b_cur,
         done, comp_time, n_ckpt, work_lost, n_kills) = st

        # -- enter cells into their next period (shorts retry next iteration)
        ent = alive & entering
        no_more = ent & (p >= cnt)
        alive = alive & ~no_more
        ent = ent & ~no_more
        pc = jnp.clip(p, 0, jnp.maximum(cnt - 1, 0))
        a = A[rows, pc]
        b = B[rows, pc]
        start_work = a + t_r
        short = ent & (start_work >= b)
        shortk = short & (b < horizon)
        n_kills = n_kills + shortk.astype(jnp.int64)
        go = ent & ~short
        t = jnp.where(go, start_work, t)
        work = jnp.where(go, saved, work)
        sv = jnp.where(go, saved, sv)
        next_dec = jnp.where(go, start_work + interval, next_dec)
        a_cur = jnp.where(go, a, a_cur)
        b_cur = jnp.where(go, b, b_cur)
        entering = entering & ~go
        p = jnp.where(short, p + 1, p)
        live = alive & ~entering

        # -- one decision tick (kernels.adapt_tick_core, the shared body)
        live, t, work, sv, next_dec, d_at, fin, ck, kl = _k.adapt_tick_core(
            jnp, live, t, work, sv, next_dec, a_cur, b_cur, work_s, t_c, t_r,
            interval, tab_flat, tab_off, tab_top, bin_s, n_bins,
        )
        comp_time = jnp.where(fin, d_at, comp_time)
        done = done | fin
        alive = alive & ~fin
        n_ckpt = n_ckpt + ck.astype(jnp.int64)
        n_kills = n_kills + kl.astype(jnp.int64)
        work_lost = jnp.where(kl, work_lost + (work - sv), work_lost)
        saved = jnp.where(kl, sv, saved)
        p = jnp.where(kl, p + 1, p)
        entering = entering | kl

        return (saved, alive, entering, p, t, work, sv, next_dec, a_cur, b_cur,
                done, comp_time, n_ckpt, work_lost, n_kills)

    st = lax.while_loop(cond, body, state0)
    (saved, _, _, p_stop, _, _, _, _, _, _,
     done, comp_time, n_ckpt, work_lost, n_kills) = st

    # -- reconstruct the run records from the final cursor state (see above)
    p_idx = jnp.arange(P)[None, :]
    short_g = (A + t_r) >= B  # NaN pads compare False
    unbilled_short = short_g & ~(B < horizon[:, None])
    p_last = jnp.where(done, p_stop, P)[:, None]
    rex = valid & (p_idx <= p_last) & ~unbilled_short
    ruser = done[:, None] & (p_idx == p_stop[:, None])
    rend = jnp.where(ruser, comp_time[:, None], B)

    state = (saved, done, comp_time, n_ckpt, work_lost,
             jnp.zeros(C, dtype=bool), n_kills)
    return state, (rex.T, rend.T, ruser.T)


# ---------------------------------------------------------------------------
# One-compile multi-scheme lax.scan program (the Pallas kernel's template)
# ---------------------------------------------------------------------------


def build_sweep_scan(schemes: tuple[Scheme, ...], count_cb=None):
    """Build the fused multi-scheme sweep program.

    One ``lax.scan`` walks the padded period axis; inside each step every
    period-synchronized scheme of the (static) ``schemes`` tuple advances its
    own state segment — scheme is a segment axis of the single trace, so the
    whole scenario is one jit-compile and one dispatch.  ADAPT, whose
    decision cadence makes the period-synchronized walk an order of magnitude
    more iterations, runs its cell-decoupled ``lax.while_loop`` twin
    (:func:`_adapt_decoupled`) inside the same program.

    All scalars are traced arguments — re-running with different simulation
    constants but the same grid shape reuses the compiled program.
    ``count_cb`` fires once per trace (the retrace-spy hook for tests).

    Returns, per scheme (in order): ``(state, (rec_exists, rec_end,
    rec_user))`` with the state 7-tuple of :func:`init_state` and records
    shaped ``(P, C)``.
    """
    schemes = tuple(schemes)
    scan_schemes = tuple(s for s in schemes if s != Scheme.ADAPT)

    def fn(
        A_T,
        B_T,
        valid_T,
        horizon,
        init_saved,
        work_s,
        t_c,
        t_r,
        hour_delta=None,
        edges_flat=None,
        edge_base=None,
        edge_n=None,
        ptr0_T=None,
        interval=None,
        tab_flat=None,
        tab_off=None,
        tab_top=None,
        bin_s=None,
        n_bins=None,
    ):
        if count_cb is not None:
            count_cb()  # Python side effect: runs at trace time only
        C = horizon.shape[0]
        c = dict(
            work_s=work_s, t_c=t_c, t_r=t_r, hour_delta=hour_delta,
            interval=interval, bin_s=bin_s, n_bins=n_bins,
            edges_flat=edges_flat, edge_base=edge_base, edge_n=edge_n,
            tab_flat=tab_flat, tab_off=tab_off, tab_top=tab_top,
        )

        def period_step(carry, xs):
            if ptr0_T is not None:
                a, b, valid, ptr0 = xs
            else:
                (a, b, valid), ptr0 = xs, None
            new_carry, recs = [], []
            for si, scheme in enumerate(scan_schemes):
                st, rec = scheme_period_step(scheme, carry[si], a, b, valid, horizon, ptr0, c)
                new_carry.append(st)
                recs.append(rec)
            return tuple(new_carry), tuple(recs)

        if scan_schemes:
            init = tuple(init_state(C, init_saved) for _ in scan_schemes)
            xs = (A_T, B_T, valid_T) + ((ptr0_T,) if ptr0_T is not None else ())
            carries, recs = lax.scan(period_step, init, xs)
        out, j = [], 0
        for scheme in schemes:
            if scheme == Scheme.ADAPT:
                out.append(
                    _adapt_decoupled(
                        A_T.T, B_T.T, valid_T.T, horizon, init_saved, work_s,
                        t_c, t_r, interval, tab_flat, tab_off, tab_top,
                        bin_s, n_bins,
                    )
                )
            else:
                out.append((carries[j], recs[j]))
                j += 1
        return tuple(out)

    return fn


# ---------------------------------------------------------------------------
# Pallas kernel: cell-blocked, period axis sequential, state in VMEM scratch
# ---------------------------------------------------------------------------


def _sweep_kernel(
    a_ref, b_ref, valid_ref, horizon_ref, ptr0_ref,
    edges_ref, ebase_ref, en_ref, tab_ref, off_ref, top_ref,
    done_ref, comp_ref, ckpt_ref, lost_ref, kills_ref,
    rex_ref, rend_ref, ruser_ref,
    saved_s, done_s, comp_s, ckpt_s, lost_s, run_s, kills_s,
    *, schemes, consts,
):
    S = len(schemes)
    blk = horizon_ref.shape[0]
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        saved_s[...] = jnp.full((S, blk), consts["init_saved"], dtype=jnp.float64)
        done_s[...] = jnp.zeros((S, blk), dtype=bool)
        comp_s[...] = jnp.full((S, blk), np.inf)
        ckpt_s[...] = jnp.zeros((S, blk), dtype=jnp.int64)
        lost_s[...] = jnp.zeros((S, blk))
        run_s[...] = jnp.zeros((S, blk), dtype=bool)
        kills_s[...] = jnp.zeros((S, blk), dtype=jnp.int64)

    a = a_ref[:, 0]
    b = b_ref[:, 0]
    valid = valid_ref[:, 0]
    horizon = horizon_ref[...]
    ptr0 = ptr0_ref[:, 0]
    c = dict(consts)
    c["edges_flat"] = edges_ref[...]
    c["edge_base"] = ebase_ref[...]
    c["edge_n"] = en_ref[...]
    c["tab_flat"] = tab_ref[...]
    c["tab_off"] = off_ref[...]
    c["tab_top"] = top_ref[...]

    for si, scheme in enumerate(schemes):
        state = (
            saved_s[si, :], done_s[si, :], comp_s[si, :], ckpt_s[si, :],
            lost_s[si, :], run_s[si, :], kills_s[si, :],
        )
        state, (rex, rend, ruser) = scheme_period_step(
            scheme, state, a, b, valid, horizon, ptr0, c
        )
        saved_s[si, :], done_s[si, :], comp_s[si, :] = state[0], state[1], state[2]
        ckpt_s[si, :], lost_s[si, :] = state[3], state[4]
        run_s[si, :], kills_s[si, :] = state[5], state[6]
        rex_ref[si, :, 0] = rex
        rend_ref[si, :, 0] = rend
        ruser_ref[si, :, 0] = ruser

    # final-state outputs: the (s, bi) block is revisited every period (its
    # index map ignores pi), so the last period's write is what lands in HBM
    done_ref[...] = done_s[...]
    comp_ref[...] = comp_s[...]
    ckpt_ref[...] = ckpt_s[...]
    lost_ref[...] = lost_s[...]
    kills_ref[...] = kills_s[...]


def _pad_cells(x, n_pad, fill):
    if n_pad == 0:
        return x
    pad = np.full((n_pad,) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def sweep_pallas(
    schemes,
    A,
    B,
    valid,
    horizon,
    consts,
    ptr0=None,
    edges=None,
    tables=None,
    block_c: int = 256,
):
    """Run the fused sweep as a Pallas kernel over cell blocks, interpreted
    (:data:`NATIVE_UNSUPPORTED` says why it is never compiled natively).

    ``A/B/valid`` are the padded ``(cells, periods)`` grid arrays, ``consts``
    the scalar dict of :func:`scheme_period_step`, ``edges`` the optional
    ``(edges_flat, edge_base, edge_n)`` EDGE arrays (with ``ptr0`` the
    ``(cells, periods)`` first-edge cursor table) and ``tables`` the optional
    ``(tab_flat, tab_off, tab_top)`` ADAPT survival tables.  Cells are padded
    to a multiple of ``block_c`` with never-available lanes (``valid=False``
    masks every update, so padding cannot change any real cell's bits).

    Returns ``(done, comp_time, n_ckpt, work_lost, n_kills)`` shaped
    ``(S, C)`` plus the run records ``(rec_exists, rec_end, rec_user)``
    shaped ``(S, C, P)``, unpadded.
    """
    schemes = tuple(schemes)
    S = len(schemes)
    C, P = A.shape
    blk = max(1, min(block_c, C))
    n_pad = (-C) % blk
    Cp = C + n_pad
    nb = Cp // blk

    A_p = _pad_cells(np.asarray(A), n_pad, np.nan)
    B_p = _pad_cells(np.asarray(B), n_pad, np.nan)
    valid_p = _pad_cells(np.asarray(valid), n_pad, False)
    horizon_p = _pad_cells(np.asarray(horizon), n_pad, 0.0)

    if ptr0 is not None:
        ptr0_p = _pad_cells(np.asarray(ptr0), n_pad, 0)
        ptr0_spec = pl.BlockSpec((blk, 1), lambda bi, pi: (bi, pi))
    else:
        ptr0_p = np.zeros((Cp, 1), dtype=np.int64)
        ptr0_spec = pl.BlockSpec((blk, 1), lambda bi, pi: (bi, 0))
    if edges is not None:
        edges_flat, edge_base, edge_n = (np.asarray(x) for x in edges)
    else:
        edges_flat = np.zeros(1)
        edge_base = np.zeros(C, dtype=np.int64)
        edge_n = np.zeros(C, dtype=np.int64)
    if tables is not None:
        tab_flat, tab_off, tab_top = (np.asarray(x) for x in tables)
    else:
        tab_flat = np.zeros(1)
        tab_off = np.zeros(C, dtype=np.int64)
        tab_top = np.zeros(C, dtype=np.int64)
    edge_base = _pad_cells(edge_base, n_pad, 0)
    edge_n = _pad_cells(edge_n, n_pad, 0)
    tab_off = _pad_cells(tab_off, n_pad, 0)
    tab_top = _pad_cells(tab_top, n_pad, 0)

    cell_spec = pl.BlockSpec((blk, 1), lambda bi, pi: (bi, pi))
    row_spec = pl.BlockSpec((blk,), lambda bi, pi: (bi,))
    final_spec = pl.BlockSpec((S, blk), lambda bi, pi: (0, bi))
    rec_spec = pl.BlockSpec((S, blk, 1), lambda bi, pi: (0, bi, pi))

    kernel = functools.partial(_sweep_kernel, schemes=schemes, consts=dict(consts))
    outs = pl.pallas_call(
        kernel,
        grid=(nb, P),
        in_specs=[
            cell_spec,  # A
            cell_spec,  # B
            cell_spec,  # valid
            row_spec,  # horizon
            ptr0_spec,  # ptr0
            pl.BlockSpec(edges_flat.shape, lambda bi, pi: (0,)),
            row_spec,  # edge_base
            row_spec,  # edge_n
            pl.BlockSpec(tab_flat.shape, lambda bi, pi: (0,)),
            row_spec,  # tab_off
            row_spec,  # tab_top
        ],
        out_specs=[
            final_spec,  # done
            final_spec,  # comp_time
            final_spec,  # n_ckpt
            final_spec,  # work_lost
            final_spec,  # n_kills
            rec_spec,  # rec_exists
            rec_spec,  # rec_end
            rec_spec,  # rec_user
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, Cp), jnp.bool_),
            jax.ShapeDtypeStruct((S, Cp), jnp.float64),
            jax.ShapeDtypeStruct((S, Cp), jnp.int64),
            jax.ShapeDtypeStruct((S, Cp), jnp.float64),
            jax.ShapeDtypeStruct((S, Cp), jnp.int64),
            jax.ShapeDtypeStruct((S, Cp, P), jnp.bool_),
            jax.ShapeDtypeStruct((S, Cp, P), jnp.float64),
            jax.ShapeDtypeStruct((S, Cp, P), jnp.bool_),
        ],
        scratch_shapes=[
            pltpu.VMEM((S, blk), dt)
            for dt in (
                jnp.float64, jnp.bool_, jnp.float64, jnp.int64,
                jnp.float64, jnp.bool_, jnp.int64,
            )
        ],
        interpret=True,
    )(
        A_p, B_p, valid_p, horizon_p, ptr0_p,
        edges_flat, edge_base, edge_n, tab_flat, tab_off, tab_top,
    )
    done, comp, ckpt, lost, kills, rex, rend, ruser = outs
    return (
        done[:, :C], comp[:, :C], ckpt[:, :C], lost[:, :C], kills[:, :C],
        rex[:, :C, :], rend[:, :C, :], ruser[:, :C, :],
    )
