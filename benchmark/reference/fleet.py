"""A fleet of jobs on the spot catalog: one scalar event loop per cell.

The benchmark's plain reference of ``repro.fleet.controller.FleetController``
on an uncontended market with fixed-margin bids, and of the fleet's type
selection and trace generation (``repro.fleet.sweep``): it imports nothing of
the program.  It reuses the reference's trace generator, biller and the
simulator's period and lease walks.

Each job replica advances through *attempts*.  A bid-limited attempt is one
availability period at the replica's bid; an ACC attempt is one lease, from
the first admissible poll tick to completion, self-termination at an hour
boundary, or the horizon.  An attempt resumes from the replica's saved work,
scaled by the type's ECU.  An out-of-bid kill or an ACC self-termination
re-places the replica (at most 64 times) on the feasible types less the one
it left and, where any remain, less those its siblings run on, at the end
plus 1e-9 s.  The first replica to finish completes the job; its siblings
are cancelled then and billed up to that instant as user terminations.

Departures from the program: none in what is computed.  The loop keeps no
telemetry, market ledger, re-bid hook or ADAPT fallback to the evaluation
trace (every type has a history here), and the attempts of a job are
simulated when placed, as in the program.
"""

from __future__ import annotations

import dataclasses
import heapq
import math

from . import billing
from . import market as ref_market
from .billing import Termination
from .policies import REFERENCE_ECU, Context, policy
from .schemes import FailurePdf, Scheme
from .simulator import _EPS, _acc_lease, _next_launch_time, _run_period
from .workload import Job

#: the fleet's histories come from this disjoint block of generator seeds
HISTORY_SEED_OFFSET = 7_654_321
#: re-placements allowed per replica
MAX_MIGRATIONS = 64
HOUR = 3600.0


def select_types(sla, n_types: int) -> list:
    """The SLA's feasible types, cheapest first within each region, taken
    round-robin over the regions in name order until ``n_types``."""
    feasible = [it for it in ref_market.catalog() if sla.admits(it)]
    by_region: dict = {}
    for it in sorted(feasible, key=lambda x: (x.on_demand, x.name)):
        by_region.setdefault(it.region, []).append(it)
    out: list = []
    while len(out) < min(n_types, len(feasible)):
        for region in sorted(by_region):
            if by_region[region] and len(out) < n_types:
                out.append(by_region[region].pop(0))
    return out


def fleet_traces(types, seeds, horizon_days: float, history: bool = False) -> dict:
    """``{seed: {type name: trace}}``; histories draw from the seeds offset
    by :data:`HISTORY_SEED_OFFSET`, so they never share a stream with the
    traces the jobs run on."""
    offset = HISTORY_SEED_OFFSET if history else 0
    models, streams, keys = [], [], []
    for it in types:
        for s in seeds:
            models.append(ref_market.TraceModel.for_instance(it))
            streams.append(ref_market.ensemble_seed(it, s + offset))
            keys.append((s, it.name))
    traces = ref_market.sample_traces_batch(models, horizon_days * 24 * HOUR, streams)
    out: dict = {s: {} for s in seeds}
    for (s, name), tr in zip(keys, traces):
        out[s][name] = tr
    return out


@dataclasses.dataclass(frozen=True)
class Attempt:
    launch: float
    end: float
    completed: bool
    killed: bool
    cost: float
    work_done: float
    saved: float
    n_ckpt: int
    self_terminated: bool = False


@dataclasses.dataclass(frozen=True)
class Record:
    """One billed instance run of one job replica (work in reference-ECU s)."""

    job_id: int
    replica: int
    instance: str
    bid: float
    launch: float
    end: float
    termination: str
    cost: float
    work_start: float
    initial_saved_ref: float
    saved_after_ref: float
    killed: bool
    completed: bool
    cancelled: bool
    self_terminated: bool


@dataclasses.dataclass(frozen=True)
class Outcome:
    job_id: int
    completed: bool
    completion_time: float
    cost: float
    n_kills: int
    n_migrations: int


def _next_available(trace, bid: float, t: float):
    """The first instant at or after ``t`` whose price admits ``bid``."""
    if t >= trace.horizon:
        return None
    i = trace.segment_index(t)
    if trace.prices[i] <= bid:
        return t
    for j in range(i + 1, len(trace.prices)):
        if trace.prices[j] <= bid:
            return float(trace.times[j])
    return None


def _next_out_of_bid(trace, bid: float, t: float) -> float:
    """The end of the availability period holding ``t``."""
    for j in range(trace.segment_index(t) + 1, len(trace.prices)):
        if trace.prices[j] > bid:
            return float(trace.times[j])
    return trace.horizon


def bid_limited_attempt(trace, scheme, work_s, bid, start_t, params, pdf, saved):
    """One availability period from the first admissible instant at or after
    ``start_t``; None when the trace never admits ``bid`` again."""
    launch = _next_available(trace, bid, start_t)
    if launch is None or launch >= trace.horizon:
        return None
    b = _next_out_of_bid(trace, bid, launch)
    killed = b < trace.horizon
    start_work = launch + params.t_r
    delta = params.billing_period_s
    if start_work >= b:  # ended before recovery finished: no progress
        cost = billing.run_cost(trace, launch, b, Termination.OUT_OF_BID, delta)
        return Attempt(launch, b, False, killed, cost, saved, saved, 0)
    done_at, work_end, saved, took = _run_period(
        trace, scheme, launch, start_work, b, saved, work_s, params, pdf)
    if done_at is not None:
        cost = billing.run_cost(trace, launch, done_at, Termination.USER, delta)
        return Attempt(launch, done_at, True, False, cost, work_s, saved, took)
    cost = billing.run_cost(trace, launch, b, Termination.OUT_OF_BID, delta)
    return Attempt(launch, b, False, killed, cost, work_end, saved, took)


def acc_attempt(trace, work_s, a_bid, start_t, params, saved):
    """One ACC lease: launched at ``t = 0`` when the price admits ``a_bid``
    there, else at the first admissible poll tick at or after ``start_t``;
    None when there is none before the horizon.  A lease that runs off the
    horizon is billed as an out-of-bid end (its partial hour free)."""
    if start_t == 0.0 and trace.price_at(0.0) <= a_bid:
        launch = 0.0
    else:
        launch = _next_launch_time(trace, start_t, a_bid, params.poll_s)
    if launch is None or launch >= trace.horizon:
        return None
    done_at, term_at, work, saved, n_ckpt = _acc_lease(trace, launch, work_s, a_bid, saved,
                                                        params)
    delta = params.billing_period_s
    if done_at is not None:
        cost = billing.run_cost(trace, launch, done_at, Termination.USER, delta)
        return Attempt(launch, done_at, True, False, cost, work_s, saved, n_ckpt)
    if term_at is None:
        cost = billing.run_cost(trace, launch, trace.horizon, Termination.OUT_OF_BID, delta)
        return Attempt(launch, trace.horizon, False, False, cost, work, saved, n_ckpt)
    cost = billing.run_cost(trace, launch, term_at, Termination.USER, delta)
    return Attempt(launch, term_at, False, False, cost, work, saved, n_ckpt, True)


@dataclasses.dataclass
class _Replica:
    saved_ref: float = 0.0
    n_migrations: int = 0
    n_kills: int = 0
    token: int | None = None
    active: tuple | None = None  # (attempt, type, bid, initial saved_ref)


def run_cell(types, traces, histories, jobs: list[Job], policy_name: str, n_replicas: int,
             margin: float, scheme: Scheme, params):
    """One cell's records, in the order the event loop emits them, and its
    outcomes, in arrival order."""
    place = policy(policy_name, n_replicas)
    ctx = Context(histories, params.t_r, margin)
    horizon = min(t.horizon for t in traces.values())
    records: list[Record] = []
    states: dict = {}  # job id -> [job, replicas, completed_at]
    heap: list = []
    seq = token = 0

    def push(t, kind, payload):
        nonlocal seq
        heapq.heappush(heap, (t, kind, seq, payload))
        seq += 1

    def feasible(job, exclude=frozenset()):
        return [it for it in types if job.sla.admits(it) and it.name not in exclude]

    def spawn(st, r, it, bid, now):
        nonlocal token
        rep = st[1][r]
        scale = REFERENCE_ECU / it.compute_units
        trace = traces[it.name]
        if scheme == Scheme.ACC:
            att = acc_attempt(trace, st[0].work_s * scale, bid, now, params,
                              rep.saved_ref * scale)
        else:
            pdf = FailurePdf.from_trace(histories[it.name], bid) if scheme == Scheme.ADAPT else None
            att = bid_limited_attempt(trace, scheme, st[0].work_s * scale, bid, now, params, pdf,
                                      rep.saved_ref * scale)
        if att is None:  # the type never admits the bid again
            return
        token += 1
        rep.token = token
        rep.active = (att, it, bid, rep.saved_ref)
        push(att.end, 1, (st[0].id, r, token))

    def record(st, r, att, it, bid, init, end, termination, cost, killed, completed, cancelled,
               saved_after, self_terminated=False):
        records.append(Record(st[0].id, r, it.name, bid, att.launch, end, termination.value, cost,
                              min(att.launch + params.t_r, end), init, saved_after, killed,
                              completed, cancelled, self_terminated))

    for job in jobs:
        push(job.arrival_s, 0, job)

    while heap:
        now, kind, _, payload = heapq.heappop(heap)
        if kind == 0:
            job = payload
            feas = feasible(job)
            if not feas:
                states[job.id] = [job, {}, None]
                continue
            ctx.prices_now = {name: tr.price_at(now) for name, tr in traces.items()}
            pls = place(job.work_s, feas, ctx)
            st = states[job.id] = [job, {r: _Replica() for r in range(len(pls))}, None]
            for r, (it, bid) in enumerate(pls):
                spawn(st, r, it, bid, now)
            continue

        job_id, r, tok = payload
        st = states[job_id]
        rep = st[1][r]
        if st[2] is not None or rep.token != tok or rep.active is None:
            continue  # cancelled or superseded
        att, it, bid, init = rep.active
        rep.token = rep.active = None
        if att.completed:
            st[2] = att.end
            record(st, r, att, it, bid, init, att.end, Termination.USER, att.cost, False, True,
                   False, st[0].work_s)
            rep.saved_ref = st[0].work_s
            for r2, rep2 in st[1].items():
                if r2 == r or rep2.active is None:
                    continue
                att2, it2, bid2, init2 = rep2.active
                rep2.token = rep2.active = None
                if att2.launch < now - _EPS:
                    cost2 = billing.run_cost(traces[it2.name], att2.launch, now, Termination.USER,
                                             params.billing_period_s)
                    record(st, r2, att2, it2, bid2, init2, now, Termination.USER, cost2, False,
                           False, True, init2)
            continue
        saved_after = att.saved / (REFERENCE_ECU / it.compute_units)
        if att.killed:
            rep.n_kills += 1
        term = Termination.USER if att.completed or att.self_terminated else Termination.OUT_OF_BID
        record(st, r, att, it, bid, init, att.end, term, att.cost, att.killed, False, False,
               saved_after, att.self_terminated)
        rep.saved_ref = saved_after
        if (att.killed or att.self_terminated) and rep.n_migrations < MAX_MIGRATIONS:
            rep.n_migrations += 1
            siblings = frozenset(rep2.active[1].name for r2, rep2 in st[1].items()
                                 if r2 != r and rep2.active is not None)
            exclude = frozenset({it.name})
            feas = feasible(st[0], exclude | siblings) or feasible(st[0], exclude)
            if not feas:
                continue
            t = att.end + _EPS
            ctx.prices_now = {name: tr.price_at(t) for name, tr in traces.items()}
            (it2, bid2), *_ = place(st[0].work_s - rep.saved_ref, feas, ctx, k=1)
            spawn(st, r, it2, bid2, t)

    per_job: dict = {}
    for rec in records:
        per_job.setdefault(rec.job_id, []).append(rec)
    outcomes = []
    for job_id, (job, reps, done_at) in states.items():
        outcomes.append(Outcome(
            job_id, done_at is not None, done_at if done_at is not None else math.inf,
            sum(rec.cost for rec in per_job.get(job_id, [])),
            sum(rep.n_kills for rep in reps.values()),
            sum(rep.n_migrations for rep in reps.values())))
    return records, outcomes
