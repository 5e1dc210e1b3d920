"""Batched ACC seek ↔ the scalar ``_next_launch_time`` walk.

The batch walker resolves each seek in one step: from the grid's launch-tick
table (``_PeriodGrid.acc_launch``) when every poll tick ``k * poll`` is a
float64 value exactly (60, 300 and 45.5 s here), and by the scalar walk per
lane when the lattice rounds (100/3 s here).  Each hand-built step trace
pins the launch ticks the scalar reaches, so the case exercises what its
name says, and every field is compared ``==`` with the scalar reference.
"""

import math

import pytest

from repro.core import Scheme, SimParams, get_instance, simulate, step_trace, synthetic_trace
from repro.core.billing import Termination
from repro.engine import BatchEngine, Scenario, assert_parity
from repro.engine import batch as batch_mod
from repro.engine.batch import _poll_lattice_exact
from repro.obs import telemetry as obs

HOUR = 3600.0
HI, MID, LO = 0.9, 0.8, 0.3  # MID and HI sit above BID, LO below it
BID = 0.5
BIDS = (0.1, BID, 1.0)  # never admitted, the case's bid, always admitted
POLLS = (60.0, 300.0, 45.5, 100.0 / 3.0)
T_W = SimParams().t_w


def tick(x: float, poll: float) -> float:
    """The first poll tick at or after ``x``."""
    return math.ceil(x / poll) * poll


def _dense_changes(p):
    # price changes every p/7, all above the bid, then the bid is admitted
    segs = [(0.0, HI)] + [(j * p / 7, MID if j % 2 else HI) for j in range(1, 37)]
    return dict(segs=segs + [(5.5 * p, LO)], launches=[6 * p])


def _dense_late(p):
    # changes every 0.4p from 60,000 s on: where the lattice rounds, the
    # walk's t + poll steps drift off k * poll and the launch tick with them
    x, a = 60000.0, 60000.0 + 20.5 * p
    segs = [(0.0, HI)] + [(x + 0.4 * p * j, MID if j % 2 else HI) for j in range(51)]
    return dict(segs=segs + [(a, LO)], launches=[tick(a, p)])


def _short_period(p):
    # [1.25p, 1.75p) holds no tick: the walk must skip it
    segs = [(0.0, HI), (1.25 * p, LO), (1.75 * p, HI), (3.5 * p, LO)]
    return dict(segs=segs, launches=[4 * p])


def _eps_above_tick(p):
    # ceil(A / poll - eps) falls on the tick just below A: launch one later
    return dict(segs=[(0.0, HI), (3 * p + 0.5e-9 * p, LO)], launches=[4 * p])


def _on_tick(p):
    return dict(segs=[(0.0, HI), (3 * p, LO)], launches=[3 * p])


def _never_admitted(p):
    return dict(segs=[(0.0, HI), (2 * p, MID), (7 * p, HI)], launches=[])


def _opens_admitted(p):
    return dict(segs=[(0.0, LO)], launches=[0.0])


def _relaunch(p):
    # price above the bid at the first hour's t_td: self-terminate at HOUR,
    # then relaunch at the first tick after the price falls again
    back = HOUR + 2.5 * p
    segs = [(0.0, LO), (HOUR - T_W - 1.0, HI), (back, LO)]
    return dict(segs=segs, launches=[0.0, tick(back, p)], work=3 * HOUR)


def _runoff(p):
    # the lease outlives the horizon: billed OUT_OF_BID over [0, horizon)
    return dict(segs=[(0.0, HI), (p, LO)], launches=[p], work=20 * HOUR, horizon=5 * HOUR)


def _saved_work(p):
    return dict(_relaunch(p), saved=HOUR)


CASES = {
    "dense_changes": _dense_changes,
    "dense_late": _dense_late,
    "short_period": _short_period,
    "eps_above_tick": _eps_above_tick,
    "on_tick": _on_tick,
    "never_admitted": _never_admitted,
    "opens_admitted": _opens_admitted,
    "relaunch": _relaunch,
    "runoff": _runoff,
    "saved_work": _saved_work,
}


@pytest.mark.parametrize("poll", POLLS, ids=lambda p: f"poll{p:g}")
@pytest.mark.parametrize("case", sorted(CASES))
def test_acc_seek_matches_scalar(case, poll, monkeypatch):
    spec = CASES[case](poll)
    work, saved = spec.get("work", 2 * HOUR), spec.get("saved", 0.0)
    tr = step_trace(spec["segs"], horizon_s=spec.get("horizon", 48 * HOUR))
    params = SimParams(poll_s=poll)

    r = simulate(tr, Scheme.ACC, work, BID, params, initial_saved_work=saved)
    assert [run.launch for run in r.runs] == pytest.approx(spec["launches"], rel=1e-12)

    walks = []
    orig = batch_mod._walk_or_nan
    monkeypatch.setattr(batch_mod, "_walk_or_nan", lambda *a: walks.append(1) or orig(*a))
    sc = Scenario.from_trace(
        tr, work, BIDS, schemes=(Scheme.ACC,), params=params, initial_saved_work=saved
    )
    assert_parity(sc, "batch")
    # the 0.1 lane never launches, so a rounding lattice always walks
    assert (not walks) == _poll_lattice_exact(poll, tr.horizon)


@pytest.mark.parametrize(
    "poll, exact", [(60.0, True), (300.0, True), (45.5, True), (100.0 / 3.0, False), (0.1, False)]
)
def test_poll_lattice_exact(poll, exact):
    assert _poll_lattice_exact(poll, 30 * 24 * HOUR) is exact


def _dense_wait():
    # the 0.5 lane waits through 2,400 price changes before its launch
    segs = [(0.0, HI)] + [(30.0 * j, MID if j % 2 else HI) for j in range(1, 2400)]
    tr = step_trace(segs + [(20 * HOUR, LO)], horizon_s=60 * HOUR)
    return tr, 5 * HOUR, BIDS


def _synthetic():
    tr = synthetic_trace(get_instance("m1.xlarge"), 20, seed=1)
    return tr, 30 * HOUR, (0.36, 0.37, 0.38)


@pytest.mark.parametrize("make", [_dense_wait, _synthetic], ids=["dense_wait", "synthetic"])
def test_acc_counters(make, monkeypatch):
    """``acc.passes`` stays with the lease hours, not the price changes, and
    ``acc.seeks`` counts every seek episode: launches plus retirements."""
    tr, work, bids = make()
    params = SimParams()
    ticks = []
    orig = batch_mod.acc_lease_tick
    monkeypatch.setattr(batch_mod, "acc_lease_tick", lambda *a: ticks.append(1) or orig(*a))
    sc = Scenario.from_trace(tr, work, bids, schemes=(Scheme.ACC,), params=params)
    tel = obs.Telemetry()
    with obs.activate(tel):
        BatchEngine().run(sc)
    passes, seeks = tel.counter("acc.passes"), tel.counter("acc.seeks")
    assert 0 < passes <= len(ticks) + seeks + 1

    launches = retirements = 0
    for bid in bids:
        r = simulate(tr, Scheme.ACC, work, bid, params)
        launches += len(r.runs)
        # a seek that found no launch: nothing ran, or the last lease self-terminated
        last_user = bool(r.runs) and r.runs[-1].termination is Termination.USER
        retirements += not r.completed and (not r.runs or last_user)
    assert seeks == launches + retirements
