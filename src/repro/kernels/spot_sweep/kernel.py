"""The fused lockstep sweep: the (type × bid × seed) grid as one program.

The paper's §VII study is a dense sweep — every (instance type, bid, scheme,
seed) cell simulated over a 30-day horizon — and its lockstep form is a scan
over the padded *period* axis with per-period checkpoint-window / decision
walks inside.  :func:`build_sweep_scan` traces it as one multi-scheme
``lax.scan`` program: scheme is a *static segment axis* of the trace, every
scheme's state tuple advances inside the same ``period_step``, so a 5-scheme
scenario compiles (and dispatches) once instead of five times.  ADAPT runs
its cell-decoupled ``lax.while_loop`` (:func:`_adapt_decoupled`) inside the
same program.

The step builds on the shared per-period orchestration
(:func:`repro.engine.kernels.period_step_masked`) and the shared pure scheme
kernels, so with x64 enabled the results are bit-identical to the NumPy
driver (:func:`repro.engine.batch.run_schemes_numpy`, the program's
reference) and to the scalar reference (asserted ``==`` by
:mod:`repro.engine.parity`).  Float64 is the parity substrate; XLA emulates
it on the TPU.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.core.schemes import Scheme
from repro.engine import kernels as _k
from repro.engine.kernels import _EPS, period_step_masked

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


def scheme_period_step(scheme: Scheme, state, a, b, valid, horizon, ptr0, c):
    """Advance one period-synchronized scheme's (NONE / OPT / HOUR / EDGE)
    state tuple through one padded period.

    ``c`` maps the traced scalar simulation constants (``work_s``, ``t_c``,
    ``t_r``, ``hour_delta``) and EDGE's flat aux arrays
    (``edges_flat``/``edge_base``/``edge_n``).  ``ptr0`` is the per-cell
    first-edge cursor for this period (EDGE only).
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
    else:  # pragma: no cover - ADAPT runs _adapt_decoupled, ACC the host
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
# One-compile multi-scheme lax.scan program
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
            edges_flat=edges_flat, edge_base=edge_base, edge_n=edge_n,
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

