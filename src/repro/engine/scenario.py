"""Declarative simulation surface: what to simulate, not how.

A :class:`Scenario` pins down one cell grid of the paper's §VII study —
market (explicit traces or a generated slice of the 64-type catalog),
workload (``work_s`` reference-ECU seconds), checkpointing schemes, bid grid,
:class:`~repro.core.schemes.SimParams`, and seeds — as a frozen value object.
Engines (:mod:`repro.engine.base`) consume a Scenario and return a
structure-of-arrays :class:`~repro.engine.base.EngineResult`; the scenario
itself never runs anything.

:class:`FleetScenario` is the fleet-study analogue: a declarative
``(policy × bid-margin × seed)`` grid over a workload stream, consumed by
:func:`repro.engine.fleetgrid.run_fleet`.

Capacity-constrained markets plug in exactly here (see
:mod:`repro.market` and docs/market.md): ``capacity`` bounds the per-type
pool, ``demand`` is the depth of the co-located foreground block a cell's
job is the marginal replica of, and materialization replaces each exogenous
trace with its auction-cleared view — so every backend (reference, batch,
jax) honors preemption-by-outbid through the one out-of-bid rule it
already implements, bit-identically.  ``capacity=None`` (the default) keeps
today's infinitely deep market, byte for byte.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Sequence

import numpy as np

from repro.core.market import (
    HOUR,
    InstanceType,
    PriceTrace,
    TraceModel,
    catalog,
    ensemble_seed,
    sample_traces_batch,
)
from repro.core.provision import SLA
from repro.core.schemes import Scheme, SimParams
from repro.market import MarketParams, effective_trace
from repro.obs import telemetry as obs

#: The bid-limited schemes (an instance lives until its spot price exceeds
#: the bid): everything except ACC, whose instances are never provider-killed.
BID_LIMITED_SCHEMES = (Scheme.NONE, Scheme.OPT, Scheme.HOUR, Scheme.EDGE, Scheme.ADAPT)

#: Schemes the array backends (batch / jax) lower onto structure-of-arrays
#: lockstep ops.  ADAPT's hazard decision became a binned-table lookup, and
#: ACC — a different control loop (bid-unlimited leases, poll-driven
#: relaunch) — runs as a cell-decoupled seek/lease state machine
#: (``engine.batch._run_acc``), so this is now *every* scheme: nothing falls
#: back to the per-cell scalar path.
BATCHED_SCHEMES = BID_LIMITED_SCHEMES + (Scheme.ACC,)


def _trace_digest(trace: PriceTrace) -> dict:
    """Content digest of a piecewise-constant trace for canonical hashing.

    The full arrays never enter the canonical form (a 30-day trace is tens of
    thousands of floats); their exact bytes do, via sha256, so any bit-level
    change to the price path changes the owning scenario's content hash.
    """
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(trace.times, dtype=np.float64).tobytes())
    h.update(np.ascontiguousarray(trace.prices, dtype=np.float64).tobytes())
    return {
        "n_segments": len(trace.prices),
        "horizon": float(trace.horizon),
        "sha256": h.hexdigest(),
    }


@dataclasses.dataclass(frozen=True)
class MarketCell:
    """One materialized (instance/trace label, seed, trace) market point.

    ``on_demand`` is the owning instance type's on-demand $/h (0.0 for
    explicit traces, which have no catalog entry) — the base that
    ``Scenario.bid_fractions`` bids are scaled by.
    """

    label: str
    seed: int
    trace: PriceTrace
    on_demand: float = 0.0


@dataclasses.dataclass(frozen=True, eq=False)
class Scenario:
    """One declarative simulation study: market × workload × schemes × bids.

    Exactly one of ``traces`` (explicit market) or ``instances`` (generated
    market) must be set.  With ``instances``, one calibrated synthetic trace
    is generated per (instance, seed) with :func:`ensemble_seed`-decorrelated
    streams; with ``traces``, ``seeds`` is ignored and each trace is one
    market cell.

    ``bids`` are absolute $/h values, exactly as the paper sweeps them
    (0.401..0.441 step 0.001 for the eu-west-1 m1.xlarge study).
    """

    work_s: float
    bids: tuple[float, ...]
    schemes: tuple[Scheme, ...] = BID_LIMITED_SCHEMES
    params: SimParams = dataclasses.field(default_factory=SimParams)
    # -- market: explicit ...
    traces: tuple[PriceTrace, ...] | None = None
    labels: tuple[str, ...] | None = None
    # -- ... or generated
    instances: tuple[InstanceType, ...] | None = None
    horizon_days: float = 30.0
    seeds: tuple[int, ...] = (0,)
    # -- workload knobs
    initial_saved_work: float = 0.0
    sla: SLA | None = None  # admission filter applied to ``instances``
    #: When True, ``bids`` are fractions of each instance's on-demand price
    #: (the paper's per-type band sweep: 0.50..0.60 straddles the calibrated
    #: base band at ~0.53 × on-demand) instead of shared absolute $/h.
    bid_fractions: bool = False
    # -- capacity-constrained market (None = today's infinitely deep pool)
    #: per-type supply: how many instances of each market cell's type exist
    capacity: int | None = None
    #: foreground block depth: the cell's job is the marginal replica of
    #: ``demand`` co-located lockstep units, so it runs only when the whole
    #: block clears the auction and pays the block's uniform clearing price
    demand: int = 1
    #: background-occupancy / displacement-ladder calibration
    market: MarketParams = dataclasses.field(default_factory=MarketParams)

    def __post_init__(self):
        if self.work_s <= 0:
            raise ValueError(f"work_s must be positive, got {self.work_s}")
        if not self.bids:
            raise ValueError("bids must be non-empty")
        if not self.schemes:
            raise ValueError("schemes must be non-empty")
        if (self.traces is None) == (self.instances is None):
            raise ValueError("set exactly one of traces= or instances=")
        if self.traces is not None and self.labels is not None:
            if len(self.labels) != len(self.traces):
                raise ValueError("labels must parallel traces")
        if self.instances is not None and not self.seeds:
            raise ValueError("seeds must be non-empty for a generated market")
        if not 0.0 <= self.initial_saved_work <= self.work_s:
            raise ValueError(
                f"initial_saved_work {self.initial_saved_work} outside [0, {self.work_s}]"
            )
        if self.bid_fractions and self.instances is None:
            raise ValueError("bid_fractions needs instances= (explicit traces have no on-demand)")
        if self.capacity is not None and self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.demand < 1:
            raise ValueError(f"demand must be >= 1, got {self.demand}")
        if self.demand > 1 and self.capacity is None:
            raise ValueError("demand > 1 needs capacity= (an infinitely deep market never clears)")

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_trace(
        trace: PriceTrace,
        work_s: float,
        bids: Sequence[float],
        schemes: Sequence[Scheme] = tuple(Scheme),
        params: SimParams | None = None,
        label: str = "trace0",
        initial_saved_work: float = 0.0,
        capacity: int | None = None,
        demand: int = 1,
        market: MarketParams | None = None,
    ) -> "Scenario":
        """Single explicit-trace study (the old ``sweep_bids`` shape)."""
        return Scenario(
            work_s=work_s,
            bids=tuple(float(b) for b in bids),
            schemes=tuple(schemes),
            params=params or SimParams(),
            traces=(trace,),
            labels=(label,),
            initial_saved_work=initial_saved_work,
            capacity=capacity,
            demand=demand,
            market=market or MarketParams(),
        )

    @staticmethod
    def grid(
        work_s: float,
        bids: Sequence[float],
        instances: Sequence[InstanceType] | None = None,
        schemes: Sequence[Scheme] = BID_LIMITED_SCHEMES,
        params: SimParams | None = None,
        horizon_days: float = 30.0,
        seeds: Sequence[int] = (0,),
        sla: SLA | None = None,
        bid_fractions: bool = False,
        capacity: int | None = None,
        demand: int = 1,
        market: MarketParams | None = None,
    ) -> "Scenario":
        """The §VII grid: (instance type × bid × seed × scheme) cells over
        generated traces.  ``instances`` defaults to the full 64-type catalog
        (filtered by ``sla`` if given).  With ``bid_fractions=True`` each bid
        is scaled by the instance's own on-demand price, sweeping every type
        around its own price band."""
        if instances is None:
            instances = catalog()
        if sla is not None:
            instances = [it for it in instances if sla.admits(it)]
        if not instances:
            raise ValueError("no instances left after SLA filter")
        return Scenario(
            work_s=work_s,
            bids=tuple(float(b) for b in bids),
            schemes=tuple(schemes),
            params=params or SimParams(),
            instances=tuple(instances),
            horizon_days=horizon_days,
            seeds=tuple(int(s) for s in seeds),
            sla=sla,
            bid_fractions=bid_fractions,
            capacity=capacity,
            demand=demand,
            market=market or MarketParams(),
        )

    # -- materialization ----------------------------------------------------

    @property
    def n_markets(self) -> int:
        if self.traces is not None:
            return len(self.traces)
        return len(self.instances) * len(self.seeds)

    @property
    def n_cells(self) -> int:
        """Total (market, bid, scheme) simulation cells."""
        return self.n_markets * len(self.bids) * len(self.schemes)

    def _clear_cell(self, cell: MarketCell) -> MarketCell:
        """Replace a cell's exogenous trace with its auction-cleared view.

        With ``capacity=None`` the cell passes through untouched (same trace
        *object* — the backward-compat contract); otherwise the cleared trace
        shares the exogenous segment boundaries and prices every segment at
        the marginal cost of the ``demand``-th foreground unit, so out-of-bid
        preemption in every backend *is* auction preemption.
        """
        if self.capacity is None:
            return cell
        cleared = effective_trace(
            cell.trace, self.capacity, self.demand, self.market, on_demand=cell.on_demand
        )
        return dataclasses.replace(cell, trace=cleared)

    def materialize(self) -> list[MarketCell]:
        """Resolve the market into concrete ``(label, seed, trace)`` cells.

        Deterministic in the scenario's fields; generated traces come from one
        batched :func:`sample_traces_batch` call with decorrelated
        :func:`ensemble_seed` streams (exactly the fleet-sweep recipe).  With
        ``capacity`` set, every cell's trace is the auction-cleared view (see
        :meth:`_clear_cell`) — the single point where contention enters, so
        all backends inherit it identically.  Timed as the ``materialize``
        span.
        """
        with obs.current().span("materialize"):
            if self.traces is not None:
                labels = self.labels or tuple(f"trace{i}" for i in range(len(self.traces)))
                return [
                    self._clear_cell(MarketCell(lbl, 0, tr))
                    for lbl, tr in zip(labels, self.traces)
                ]
            models, streams = [], []
            for it in self.instances:
                m = TraceModel.for_instance(it)
                for s in self.seeds:
                    models.append(m)
                    streams.append(ensemble_seed(it, s))
            traces = sample_traces_batch(models, self.horizon_days * 24 * HOUR, streams)
            cells: list[MarketCell] = []
            k = 0
            for it in self.instances:
                for s in self.seeds:
                    cells.append(self._clear_cell(MarketCell(it.name, s, traces[k], it.on_demand)))
                    k += 1
            return cells

    def materialize_cell(self, market: int) -> MarketCell:
        """Resolve a single market cell without generating the whole grid.

        Bitwise-identical to ``materialize()[market]``: generated traces come
        from the same :func:`sample_traces_batch` streams, which are
        deterministic per (model, seed) regardless of batch composition.
        Useful when one cell feeds a live run (e.g.
        ``SpotTrainer.from_scenario``) — a 64-type × many-seed scenario
        shouldn't generate 256 traces to use one.
        """
        if self.traces is not None:
            labels = self.labels or tuple(f"trace{i}" for i in range(len(self.traces)))
            return self._clear_cell(MarketCell(labels[market], 0, self.traces[market]))
        it = self.instances[market // len(self.seeds)]
        seed = self.seeds[market % len(self.seeds)]
        trace = sample_traces_batch(
            [TraceModel.for_instance(it)],
            self.horizon_days * 24 * HOUR,
            [ensemble_seed(it, seed)],
        )[0]
        return self._clear_cell(MarketCell(it.name, seed, trace, it.on_demand))

    def market_bids(self, market: MarketCell) -> tuple[float, ...]:
        """Absolute $/h bids for one market cell (scaled when
        ``bid_fractions`` is set; the $0.001 grid rounding matches the
        catalog's price grid)."""
        if not self.bid_fractions:
            return self.bids
        return tuple(round(f * market.on_demand, 3) for f in self.bids)

    def canonical(self) -> dict:
        """Stable plain-dict form of every engine-visible field.

        The contract backing :mod:`repro.suite.hashing`: two scenarios are
        equal-as-simulations iff their canonical dicts are equal.  The form is
        independent of construction route (``Scenario.grid`` vs the raw
        constructor vs a suite spec) and of any mapping order — consumers
        serialize it with sorted keys.  Explicit traces enter as content
        digests (:func:`_trace_digest`); every numeric field is normalized to
        ``float``/``int`` so a spec that writes ``300`` and one that writes
        ``300.0`` hash identically.
        """
        return {
            "kind": "scenario",
            "work_s": float(self.work_s),
            "bids": [float(b) for b in self.bids],
            "schemes": [s.value for s in self.schemes],
            "params": {k: float(v) for k, v in dataclasses.asdict(self.params).items()},
            "traces": None
            if self.traces is None
            else [_trace_digest(t) for t in self.traces],
            "labels": None if self.labels is None else [str(x) for x in self.labels],
            "instances": None
            if self.instances is None
            else [
                {
                    "name": it.name,
                    "hardware": it.hardware,
                    "region": it.region,
                    "os": it.os,
                    "on_demand": float(it.on_demand),
                    "compute_units": float(it.compute_units),
                }
                for it in self.instances
            ],
            "horizon_days": float(self.horizon_days),
            "seeds": [int(s) for s in self.seeds],
            "initial_saved_work": float(self.initial_saved_work),
            "sla": None
            if self.sla is None
            else {
                "min_compute_units": float(self.sla.min_compute_units),
                "regions": [str(r) for r in self.sla.regions],
                "os": self.sla.os,
            },
            "bid_fractions": bool(self.bid_fractions),
            "capacity": None if self.capacity is None else int(self.capacity),
            "demand": int(self.demand),
            "market": _canonical_market_params(self.market),
        }


def _canonical_market_params(params: MarketParams) -> dict:
    d = dataclasses.asdict(params)
    return {k: (None if v is None else float(v)) for k, v in d.items()}


@dataclasses.dataclass(frozen=True, eq=False)
class FleetScenario:
    """Declarative fleet study: (policy × bid-margin × seed) over a job stream.

    The frozen analogue of the legacy ``repro.fleet.sweep.SweepConfig`` with
    the policy set folded in.  ``policies`` names placement policies from
    :func:`repro.engine.fleetgrid.policy_registry`; pass policy *objects*
    directly to :func:`repro.engine.fleetgrid.run_fleet` to override.
    """

    n_jobs: int = 50
    mean_interarrival_s: float = 0.5 * HOUR
    mean_work_h: float = 4.0
    horizon_days: float = 10.0
    n_types: int = 16
    seeds: tuple[int, ...] = (0, 1, 2, 3)
    bid_margins: tuple[float, ...] = (0.56,)
    scheme: Scheme = Scheme.HOUR
    sla: SLA = dataclasses.field(default_factory=lambda: SLA(min_compute_units=4.0, os="linux"))
    n_replicas: int = 2
    deadline_slack: float | None = 4.0
    policies: tuple[str, ...] = ("algorithm1", "cost_greedy", "eet_greedy", "diversified")
    # -- capacity-constrained market (None = today's infinitely deep pools)
    #: per-type supply; with it set the controller registers every placement
    #: as demand, so large fleets move prices against themselves and each
    #: other, and rising clearing prices preempt outbid replicas
    capacity: int | None = None
    #: background/displacement calibration shared by every type's pool
    market: MarketParams = dataclasses.field(default_factory=MarketParams)
    #: online bid policy: ``"fixed"`` = today's ``bid_margin × on-demand``;
    #: ``"rebid"`` re-bids from the currently cleared spot quote on every
    #: (re-)placement (see :class:`repro.fleet.policies.ClearingRebid`)
    bid_policy: str = "fixed"
    #: markup over the cleared quote used by ``bid_policy="rebid"``
    rebid_markup: float = 0.10

    def __post_init__(self):
        if self.n_jobs <= 0 or self.n_types <= 0:
            raise ValueError("n_jobs and n_types must be positive")
        if not self.seeds or not self.bid_margins or not self.policies:
            raise ValueError("seeds, bid_margins and policies must be non-empty")
        if self.capacity is not None and self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.bid_policy not in ("fixed", "rebid"):
            raise ValueError(f"unknown bid_policy {self.bid_policy!r}; expected fixed|rebid")

    @staticmethod
    def from_sweep_config(cfg, policies: Sequence[str] | None = None) -> "FleetScenario":
        """Lift a legacy ``SweepConfig`` into the declarative surface."""
        kwargs = {}
        if policies is not None:
            kwargs["policies"] = tuple(policies)
        return FleetScenario(
            n_jobs=cfg.n_jobs,
            mean_interarrival_s=cfg.mean_interarrival_s,
            mean_work_h=cfg.mean_work_h,
            horizon_days=cfg.horizon_days,
            n_types=cfg.n_types,
            seeds=tuple(cfg.seeds),
            bid_margins=tuple(cfg.bid_margins),
            scheme=cfg.scheme,
            sla=cfg.sla,
            n_replicas=cfg.n_replicas,
            deadline_slack=cfg.deadline_slack,
            **kwargs,
        )

    def canonical(self) -> dict:
        """Stable plain-dict form for content hashing (see
        :meth:`Scenario.canonical` for the contract)."""
        return {
            "kind": "fleet",
            "n_jobs": int(self.n_jobs),
            "mean_interarrival_s": float(self.mean_interarrival_s),
            "mean_work_h": float(self.mean_work_h),
            "horizon_days": float(self.horizon_days),
            "n_types": int(self.n_types),
            "seeds": [int(s) for s in self.seeds],
            "bid_margins": [float(m) for m in self.bid_margins],
            "scheme": self.scheme.value,
            "sla": {
                "min_compute_units": float(self.sla.min_compute_units),
                "regions": [str(r) for r in self.sla.regions],
                "os": self.sla.os,
            },
            "n_replicas": int(self.n_replicas),
            "deadline_slack": None if self.deadline_slack is None else float(self.deadline_slack),
            "policies": [str(p) for p in self.policies],
            "capacity": None if self.capacity is None else int(self.capacity),
            "market": _canonical_market_params(self.market),
            "bid_policy": str(self.bid_policy),
            "rebid_markup": float(self.rebid_markup),
        }
