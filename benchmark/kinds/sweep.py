"""Sweep studies: one ``Scenario`` over the (type x bid x seed x scheme) grid.

A study is ``repro.engine.run(scenario, engine="jax")`` on a scenario built
anew from the configuration and the run's seed, timed from the scenario's
construction to the result grid on the host.  What decides ``correct`` is the
comparison of the window's results, at cells drawn from the seed, with the
scalar reference simulator in :mod:`benchmark.reference`, run on traces that
the reference generates itself.
"""

from __future__ import annotations


import numpy as np

from benchmark.kinds import held_in, seed_rng
from benchmark.reference import market as ref_market
from benchmark.reference import simulator as ref_sim
from benchmark.reference.schemes import Scheme as RefScheme
from benchmark.reference.schemes import SimParams as RefParams
from benchmark.roofline import sweep_bytes

#: fields compared, as ``EngineResult`` names them; the first four are counts or flags
DISCRETE = ("completed", "n_checkpoints", "n_kills", "n_self_terminations")
FLOATS = ("completion_time", "work_lost_s", "cost")

#: Limits of the compared numbers (PERF.md gives the readings each was set
#: from): sampled scheme-cells whose counts or flags differ from the
#: reference, the widest time gap as a share of the horizon and the widest
#: cost gap as a share of the reference cost, both where the counts agree.
LIMITS = {"discrete": 40, "time_gap": 1e-7, "cost_gap": 1e-7}

#: smallest cost a cost gap is taken against: one step of the $0.001 price grid
COST_FLOOR = 1e-3


class Study:
    """One sweep cell: the configuration, the traffic and the run's seed."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        rng = seed_rng(seed)
        names = [it.name for it in ref_market.catalog()]
        if config["instances"] != "catalog":
            names = list(config["instances"])
        self.instance_names = [names[i] for i in rng.permutation(len(names))]
        seeds = list(config["ensemble_seeds"])
        self.ensemble_seeds = [seeds[i] for i in rng.permutation(len(seeds))]
        self.bids = tuple(float(b) for b in config["bid_fractions"])
        self.schemes = tuple(traffic["schemes"])
        n_markets = len(self.instance_names) * len(self.ensemble_seeds)
        k = min(int(traffic["reference_cells"]), n_markets * len(self.bids))
        flat = np.sort(rng.choice(n_markets * len(self.bids), size=k, replace=False))
        self.sample = (flat // len(self.bids), flat % len(self.bids))
        self.horizon_s = float(config["horizon_days"]) * 86400.0

    # -- the timed path ------------------------------------------------------

    def scenario(self):
        """A new ``Scenario`` from the configuration and the seed's order."""
        from repro.core.market import catalog
        from repro.core.schemes import Scheme, SimParams
        from repro.engine import Scenario

        by_name = {it.name: it for it in catalog()}
        return Scenario.grid(
            work_s=float(self.config["work_h"]) * 3600.0,
            bids=self.bids,
            instances=[by_name[n] for n in self.instance_names],
            schemes=[Scheme(s) for s in self.schemes],
            params=SimParams(**self.config["params"]),
            horizon_days=float(self.config["horizon_days"]),
            seeds=self.ensemble_seeds,
            bid_fractions=True,
        )

    def run(self, tel):
        """One study, from the scenario's construction to the result grid on
        the host (``EngineResult`` arrays are NumPy arrays)."""
        from repro.engine import run

        return run(self.scenario(), engine="jax")

    def own_work(self, tel, out) -> str | None:
        """Why the study did not do its own work, or None when it did: it
        built its period grid and ran the device program."""
        if not tel.find_spans("grid.periods"):
            return "no grid.periods span: the period grid came from a cache"
        if not any(s.attrs.get("impl") == "scan" for s in tel.find_spans("sim")):
            return "no sim span with impl=scan: the device sweep did not run"
        return None

    def keep(self, out) -> dict:
        """The sampled cells of one study's result, ``(cells, schemes)`` per field."""
        m, b = self.sample
        return {f: np.array(getattr(out, f)[m, b, :]) for f in DISCRETE + FLOATS}

    # -- the reference ----------------------------------------------------------

    def _ref_traces(self):
        """The markets in the scenario's order, from the reference's generator."""
        by_name = {it.name: it for it in ref_market.catalog()}
        models, streams, on_demand = [], [], []
        for name in self.instance_names:
            it = by_name[name]
            for s in self.ensemble_seeds:
                models.append(ref_market.TraceModel.for_instance(it))
                streams.append(ref_market.ensemble_seed(it, s))
                on_demand.append(it.on_demand)
        traces = ref_market.sample_traces_batch(models, self.horizon_s, streams)
        return traces, on_demand

    def reference(self, precision=np.float64) -> dict:
        """The scalar reference at the sampled cells.  ``precision=np.float32``
        is the control: every trace time and price, and every result, held
        in float32."""
        traces, on_demand = self._ref_traces()
        if precision is not np.float64:
            traces = [held_in(t, precision) for t in traces]
        params = RefParams(**self.config["params"])
        work_s = float(self.config["work_h"]) * 3600.0
        m_idx, b_idx = self.sample
        out = {f: np.zeros((len(m_idx), len(self.schemes))) for f in DISCRETE + FLOATS}
        for i, (m, b) in enumerate(zip(m_idx, b_idx)):
            bid = round(self.bids[b] * on_demand[m], 3)
            for s, scheme in enumerate(self.schemes):
                r = ref_sim.simulate(traces[m], RefScheme(scheme), work_s, bid, params)
                for f in DISCRETE + FLOATS:
                    out[f][i, s] = getattr(r, f)
        if precision is not np.float64:
            out = {f: v.astype(precision).astype(np.float64) for f, v in out.items()}
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
        """P, C and the rising-edge count of a study's grid, from the
        reference's traces: the shapes the sweep's least bytes follow."""
        traces, on_demand = self._ref_traces()
        periods = 0
        edges = 0
        for tr, od in zip(traces, on_demand):
            bids = np.array([round(f * od, 3) for f in self.bids])
            ok = tr.prices[None, :] <= bids[:, None]
            starts = np.diff(ok.astype(np.int8), axis=1) == 1
            periods = max(periods, int((starts.sum(axis=1) + ok[:, 0]).max()))
            edges += int((np.diff(tr.prices) > 0).sum())
        n_cells = len(traces) * len(self.bids)
        schemes = [s for s in self.schemes if s != "acc"]
        return {"P": max(periods, 1), "C": n_cells, "E": edges, "scan_schemes": schemes,
                "sweep_bytes_per_study": sweep_bytes(max(periods, 1), n_cells, edges, schemes)}


def compare(ref: dict, got: dict, horizon_s: float) -> dict:
    """Compare one study's sampled cells with the reference.

    ``discrete`` counts scheme-cells whose counts or flags differ (a NaN, or a
    completion time finite on one side only, counts there too); ``time_gap``
    is the widest completion-time or work-lost gap over the horizon, and
    ``cost_gap`` the widest cost gap over the reference cost (at least
    :data:`COST_FLOOR`), both over the cells whose counts agree."""
    bad = np.zeros(ref["cost"].shape, dtype=bool)
    for f in DISCRETE:
        bad |= np.asarray(got[f]) != np.asarray(ref[f])
    for f in FLOATS:
        g, r = np.asarray(got[f], dtype=np.float64), np.asarray(ref[f], dtype=np.float64)
        bad |= np.isnan(g) | (np.isfinite(g) != np.isfinite(r))
    ok = ~bad
    time_gap = 0.0
    for f in ("completion_time", "work_lost_s"):
        g, r = np.asarray(got[f], dtype=np.float64), np.asarray(ref[f], dtype=np.float64)
        both = ok & np.isfinite(r)
        if both.any():
            time_gap = max(time_gap, float(np.max(np.abs(g[both] - r[both]))) / horizon_s)
    g, r = np.asarray(got["cost"], dtype=np.float64), np.asarray(ref["cost"], dtype=np.float64)
    cost_gap = 0.0
    if ok.any():
        cost_gap = float(np.max(np.abs(g[ok] - r[ok]) / np.maximum(np.abs(r[ok]), COST_FLOOR)))
    return {"discrete": int(bad.sum()), "time_gap": time_gap, "cost_gap": cost_gap}
