"""Fleet studies: one ``FleetScenario`` over the (policy x bid margin x seed) grid.

A study is ``repro.engine.run_fleet(scenario, engine="jax")`` on a scenario
built anew from the configuration, the traffic mix and the run's seed, timed
from the scenario's construction to every cell's records and outcomes on the
host.  The program keeps the inputs of its last few scenarios
(``fleetgrid._INPUTS_CACHE``: traces, histories, workloads and the placement
memo), so each study empties that pool first and must show it built them
again (a ``fleet.inputs`` span) and scored placements on the device (a
``fleet.score`` span with ``impl=jax``).

What decides ``correct`` is the comparison of the window's results, at cells
drawn from the seed, with the scalar event loop in
:mod:`benchmark.reference.fleet`, run on traces and job streams that the
reference generates itself.
"""

from __future__ import annotations

import inspect
import math

import numpy as np

from benchmark.kinds import held_in, seed_rng
from benchmark.reference import fleet as ref_fleet
from benchmark.reference.schemes import Scheme as RefScheme
from benchmark.reference.schemes import SimParams as RefParams
from benchmark.reference.workload import SLA as RefSLA
from benchmark.reference.workload import poisson_stream

#: Limits of the compared numbers (PERF.md gives the readings each was set
#: from): the worst study's count of record and outcome fields that differ
#: from the reference (counts, flags, instance, termination, and records or
#: outcomes one side lacks), its widest time gap (launch, end, work start,
#: saved work, completion) over the horizon, and its widest cost gap (record
#: and outcome costs, bids) over the reference value, where the fields agree.
LIMITS = {"discrete": 4, "time_gap": 1e-9, "cost_gap": 1e-9}

#: smallest cost a cost gap is taken against: one step of the $0.001 price grid
COST_FLOOR = 1e-3

#: record fields, as ``AttemptRecord`` names them, by how they are compared
REC_DISCRETE = ("replica", "instance", "termination", "killed", "completed", "cancelled",
                "self_terminated")
REC_TIMES = ("launch", "end", "work_start", "initial_saved_ref", "saved_after_ref")
REC_COSTS = ("bid", "cost")
#: outcome fields, as ``JobOutcome`` names them
OUT_DISCRETE = ("completed", "n_kills", "n_migrations")


class Study:
    """One fleet cell: the configuration, the traffic and the run's seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.schemes import SimParams
        from repro.fleet.workload import Workload

        if SimParams(**config["params"]) != SimParams():
            raise ValueError("the fleet runs SimParams' defaults; the configuration's params "
                             f"{config['params']} differ")
        sigma = inspect.signature(Workload.poisson).parameters["work_sigma"].default
        if traffic["work_sigma"] != sigma:
            raise ValueError(f"the fleet draws work with log-sd {sigma}, the traffic asks for "
                             f"{traffic['work_sigma']}")
        self.config, self.traffic = config, traffic
        rng = seed_rng(seed)
        seeds, margins, policies = (list(config[k]) for k in
                                    ("ensemble_seeds", "bid_margins", "policies"))
        self.ensemble_seeds = [seeds[i] for i in rng.permutation(len(seeds))]
        self.margins = [margins[i] for i in rng.permutation(len(margins))]
        self.policies = [policies[i] for i in rng.permutation(len(policies))]
        cells = [(p, m, s) for s in seeds for m in margins for p in policies]
        k = min(int(traffic["reference_cells"]), len(cells))
        self.sample = [cells[i] for i in np.sort(rng.choice(len(cells), size=k, replace=False))]
        self.horizon_s = float(config["horizon_days"]) * 86400.0

    def _result_key(self, cell):
        """The program's key of a cell: its policy object's name."""
        p, m, s = cell
        return (f"diversified{self.config['n_replicas']}" if p == "diversified" else p, m, s)

    # -- the timed path ------------------------------------------------------

    def scenario(self):
        """A new ``FleetScenario`` from the configuration, traffic and order."""
        from repro.core.provision import SLA
        from repro.core.schemes import Scheme
        from repro.engine import FleetScenario

        c, t = self.config, self.traffic
        return FleetScenario(
            n_jobs=int(t["n_jobs"]),
            mean_interarrival_s=float(t["mean_interarrival_h"]) * 3600.0,
            mean_work_h=float(c["work_h"]),
            horizon_days=float(c["horizon_days"]),
            n_types=int(c["n_types"]),
            seeds=tuple(self.ensemble_seeds),
            bid_margins=tuple(self.margins),
            scheme=Scheme(t["scheme"]),
            sla=SLA(**c["sla"]),
            n_replicas=int(c["n_replicas"]),
            deadline_slack=float(t["deadline_slack"]),
            policies=tuple(self.policies),
        )

    def run(self, tel):
        """One study, its inputs built anew: from the scenario's construction
        to every cell's records and outcomes on the host."""
        from repro.engine import fleetgrid, run_fleet

        fleetgrid._INPUTS_CACHE.clear()
        return run_fleet(self.scenario(), engine="jax")

    def own_work(self, tel, out) -> str | None:
        """Why the study did not do its own work, or None when it did: it
        built its inputs and scored placements on the device."""
        if not tel.find_spans("fleet.inputs"):
            return "no fleet.inputs span: the fleet's inputs came from a cache"
        if not any(s.attrs.get("impl") == "jax" for s in tel.find_spans("fleet.score")):
            return "no fleet.score span with impl=jax: no placement was scored on the device"
        return None

    def keep(self, out) -> dict:
        """The sampled cells of one study: per cell its records and outcomes
        as plain tuples (``_record_row`` / ``_outcome_row``)."""
        kept = {}
        for cell in self.sample:
            res = out.results[self._result_key(cell)]
            kept[cell] = ([_record_row(r, r.termination.value) for r in res.records],
                          [_outcome_row(o, j) for j, o in res.outcomes.items()])
        return kept

    # -- the reference ----------------------------------------------------------

    def reference(self, precision=np.float64) -> dict:
        """The scalar reference at the sampled cells: per cell its records
        and outcomes as :meth:`keep` holds them.  ``precision=np.float32`` is
        the control: every trace and history time and price, and every
        result, held in float32."""
        c, t = self.config, self.traffic
        sla = RefSLA(**c["sla"])
        types = ref_fleet.select_types(sla, int(c["n_types"]))
        seeds = sorted({s for _, _, s in self.sample})
        traces = ref_fleet.fleet_traces(types, seeds, float(c["horizon_days"]))
        hists = ref_fleet.fleet_traces(types, seeds, float(c["horizon_days"]), history=True)
        if precision is not np.float64:
            traces, hists = ({s: {n: held_in(tr, precision) for n, tr in by.items()}
                              for s, by in d.items()} for d in (traces, hists))
        out = {}
        for p, m, s in self.sample:
            jobs = poisson_stream(int(t["n_jobs"]), float(t["mean_interarrival_h"]) * 3600.0,
                                  float(c["work_h"]) * 3600.0, s, sla, float(t["work_sigma"]),
                                  float(t["deadline_slack"]))
            records, outcomes = ref_fleet.run_cell(
                types, traces[s], hists[s], jobs, p, int(c["n_replicas"]), m,
                RefScheme(t["scheme"]), RefParams(**c["params"]))
            rows = ([_record_row(r, r.termination) for r in records],
                    [_outcome_row(o, o.job_id) for o in outcomes])
            if precision is not np.float64:
                rows = tuple([_held(row, precision) for row in part] for part in rows)
            out[(p, m, s)] = rows
        return out

    def checks(self, kept: list[dict], ref: dict | None = None) -> dict:
        """The compared numbers over every study of the window: the worst
        study's reading of each."""
        ref = self.reference() if ref is None else ref
        readings = [compare(ref, got, self.horizon_s) for got in kept]
        return {name: max(r[name] for r in readings) for name in LIMITS}

    def control(self) -> dict:
        """The control's readings: the float32 reference in the program's place."""
        return self.checks([self.reference(np.float32)])

    def shapes(self, kept=None) -> dict:
        """Nothing: the EET program's least bytes follow its own counter
        (``fleet_step.cells``), which the reader takes from the studies."""
        return {}


def _record_row(r, termination) -> tuple:
    """A record as ``(job_id, discrete fields, times, costs)``."""
    return (r.job_id, tuple(termination if f == "termination" else getattr(r, f)
                            for f in REC_DISCRETE),
            tuple(float(getattr(r, f)) for f in REC_TIMES),
            tuple(float(getattr(r, f)) for f in REC_COSTS))


def _outcome_row(o, job_id) -> tuple:
    """An outcome as ``(job_id, discrete fields, (completion time,), (cost,))``."""
    return (job_id, tuple(getattr(o, f) for f in OUT_DISCRETE),
            (float(o.completion_time),), (float(o.cost),))


def _held(row, precision) -> tuple:
    """A row with its times and costs held in ``precision``."""
    job, disc, times, costs = row
    return (job, disc, tuple(float(precision(x)) for x in times),
            tuple(float(precision(x)) for x in costs))


def _pair_rows(ref_rows, got_rows) -> tuple[int, list]:
    """Rows paired per job in order; returns the count of rows one side
    lacks and the pairs."""
    by_job: dict = {}
    for side, rows in enumerate((ref_rows, got_rows)):
        for row in rows:
            by_job.setdefault(row[0], ([], []))[side].append(row)
    missing, pairs = 0, []
    for ref_job, got_job in by_job.values():
        missing += abs(len(ref_job) - len(got_job))
        pairs.extend(zip(ref_job, got_job))
    return missing, pairs


def compare(ref: dict, got: dict, horizon_s: float) -> dict:
    """Compare one study's sampled cells with the reference.

    ``discrete`` counts records and outcomes one side lacks and the pairs
    whose counts, flags, instance or termination differ (a NaN, or a time
    finite on one side only, counts there too); ``time_gap`` is the widest
    time gap over the horizon and ``cost_gap`` the widest cost or bid gap over
    the reference value (at least :data:`COST_FLOOR`), both over the pairs
    that agree otherwise."""
    discrete, time_gap, cost_gap = 0, 0.0, 0.0
    for cell, (ref_recs, ref_outs) in ref.items():
        got_recs, got_outs = got[cell]
        for ref_rows, got_rows in ((ref_recs, got_recs), (ref_outs, got_outs)):
            missing, pairs = _pair_rows(ref_rows, got_rows)
            discrete += missing
            for (_, rd, rt, rc), (_, gd, gt, gc) in pairs:
                bad = rd != gd or any(math.isnan(g) or math.isfinite(g) != math.isfinite(r)
                                      for g, r in zip(gt + gc, rt + rc))
                if bad:
                    discrete += 1
                    continue
                for g, r in zip(gt, rt):
                    if math.isfinite(r):
                        time_gap = max(time_gap, abs(g - r) / horizon_s)
                for g, r in zip(gc, rc):
                    cost_gap = max(cost_gap, abs(g - r) / max(abs(r), COST_FLOOR))
    return {"discrete": discrete, "time_gap": time_gap, "cost_gap": cost_gap}
