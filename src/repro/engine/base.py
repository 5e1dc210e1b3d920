"""Engine protocol and the structure-of-arrays result container.

An :class:`Engine` consumes a :class:`~repro.engine.scenario.Scenario` and
returns an :class:`EngineResult` — per-cell outcome arrays shaped
``(n_markets, n_bids, n_schemes)``.  Three interchangeable backends ship:

  * :class:`~repro.engine.reference.ReferenceEngine` — wraps the scalar
    event loop of :func:`repro.core.simulator.simulate`; the semantic anchor.
  * :class:`~repro.engine.batch.BatchEngine` — lowers every bid-limited
    scheme (ADAPT included, via binned hazard tables) onto lockstep NumPy
    ops; bit-identical to the reference on ``cost`` / ``completion_time`` /
    ``n_kills`` / ``n_checkpoints`` (enforced by :mod:`repro.engine.parity`
    and the CI benchmark gate).
  * :class:`~repro.engine.jax_backend.JaxEngine` — the fused multi-scheme
    spot-sweep program (one jit compile for the whole scheme set) on
    ``jax.numpy`` with x64 (``engine="jax"``); same parity contract on CPU,
    not on a TPU (emulated float64).

``run(scenario)`` is the one-call surface; ``engine="auto"`` picks the batch
backend on every platform (see :func:`get_engine` for why not the jax
backend on a TPU).  Every scheme is batched — ACC included — so no backend
falls back to the scalar reference for any cell.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Protocol, runtime_checkable

import numpy as np

from repro.core.schemes import Scheme
from repro.core.simulator import SimResult
from repro.engine.scenario import MarketCell, Scenario
from repro.obs.telemetry import Span, Telemetry

#: SimResult fields every backend must agree on, cell for cell.
PARITY_FIELDS = ("completed", "completion_time", "cost", "n_checkpoints", "n_kills")


@dataclasses.dataclass(frozen=True)
class SchemePhases:
    """One scheme's wall-time split inside an engine run."""

    sim_s: float = 0.0
    bill_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class PhaseTimings:
    """Typed per-phase breakdown of one engine run, built from the span tree.

    Every backend populates :attr:`EngineResult.timings` with one of these
    (the old free-form dict is gone).  Phases that a backend does not have
    stay at their zero defaults: the fused device backends report one
    ``sim_s`` covering all schemes, the NumPy batch driver reports per-scheme
    ``per_scheme[name].sim_s`` instead, the scalar reference engine reports
    ``scalar_s``.
    """

    engine: str
    total_s: float
    grid_s: float = 0.0  # period grid + ADAPT tables (cache misses only)
    sim_s: float = 0.0  # fused one-compile sim phase (jax)
    scalar_s: float = 0.0  # scalar event-loop phase (reference engine)
    impl: str | None = None  # spot_sweep implementation label, when applicable
    per_scheme: Mapping[str, SchemePhases] = dataclasses.field(default_factory=dict)

    @property
    def bill_s(self) -> float:
        """Total billing wall time across schemes."""
        return sum(p.bill_s for p in self.per_scheme.values())

    @property
    def sim_total_s(self) -> float:
        """Simulation wall time whichever way the backend phases it."""
        return self.sim_s + sum(p.sim_s for p in self.per_scheme.values())

    def asdict(self) -> dict:
        """JSON-ready form (bench history records)."""
        d = dataclasses.asdict(self)
        d["per_scheme"] = {k: dataclasses.asdict(v) for k, v in self.per_scheme.items()}
        return d

    @classmethod
    def from_span(cls, root: Span, engine: str, total_s: float) -> "PhaseTimings":
        """Fold an ``engine.run`` span subtree into the typed record.

        Span conventions (see docs/observability.md): ``grid`` wraps the
        period-grid/tables build, ``sim`` wraps simulation (with a
        ``scheme`` attr on per-scheme backends, an ``impl`` attr on the
        fused ones), ``bill`` wraps billing per scheme, ``scalar`` wraps the
        scalar event-loop fill.  ``sim`` spans exclude their nested ``bill``
        children and keep every other child (the fused sweep's ``sim.*``
        phases are simulation time).
        """
        grid_s = scalar_s = sim_s = 0.0
        impl = None
        per: dict[str, dict[str, float]] = {}

        def bucket(scheme: str) -> dict[str, float]:
            return per.setdefault(scheme, {"sim_s": 0.0, "bill_s": 0.0})

        for s in root.find("grid"):
            grid_s += s.dur
        for s in root.find("scalar"):
            scalar_s += s.dur
        for s in root.find("sim"):
            if "impl" in s.attrs:
                impl = s.attrs["impl"]
            own = s.dur - sum(c.dur for c in s.children if c.name == "bill")
            if "scheme" in s.attrs:
                bucket(s.attrs["scheme"])["sim_s"] += own
            else:
                sim_s += own
        for s in root.find("bill"):
            if "scheme" in s.attrs:
                bucket(s.attrs["scheme"])["bill_s"] += s.dur
        return cls(
            engine=engine,
            total_s=total_s,
            grid_s=grid_s,
            sim_s=sim_s,
            scalar_s=scalar_s,
            impl=impl,
            per_scheme={k: SchemePhases(**v) for k, v in per.items()},
        )


@dataclasses.dataclass
class EngineResult:
    """SoA outcome grid: axis 0 markets, axis 1 bids, axis 2 schemes.

    ``sim_results`` is populated by the reference backend only (it is the one
    that materializes per-run records); the batch backend leaves it ``None``
    and :meth:`cell` reconstructs a run-less :class:`SimResult`.
    """

    scenario: Scenario
    engine: str
    markets: list[MarketCell]
    bids: tuple[float, ...]
    schemes: tuple[Scheme, ...]
    completed: np.ndarray  # bool  (M, B, S)
    completion_time: np.ndarray  # float64, inf when unfinished
    cost: np.ndarray  # float64 $
    n_checkpoints: np.ndarray  # int64
    n_kills: np.ndarray  # int64
    n_self_terminations: np.ndarray  # int64 (ACC only)
    work_lost_s: np.ndarray  # float64
    wall_s: float = 0.0
    sim_results: dict[tuple[int, int, int], SimResult] | None = None
    #: typed phase-timing breakdown (grid build, per-scheme sim vs billing,
    #: scalar fill) built from the run's span tree; populated by **every**
    #: backend (``engine_bench --profile`` renders it)
    timings: PhaseTimings | None = None

    @property
    def shape(self) -> tuple[int, int, int]:
        return self.cost.shape

    @property
    def n_cells(self) -> int:
        return int(np.prod(self.shape))

    @property
    def cells_per_s(self) -> float:
        return self.n_cells / self.wall_s if self.wall_s > 0 else math.inf

    def scheme_index(self, scheme: Scheme) -> int:
        return self.schemes.index(scheme)

    def cell(self, market: int, bid: int, scheme: Scheme | int) -> SimResult:
        """Reconstruct one cell as a :class:`SimResult` (runs only when the
        backend kept them)."""
        s = scheme if isinstance(scheme, int) else self.scheme_index(scheme)
        if self.sim_results is not None and (market, bid, s) in self.sim_results:
            return self.sim_results[(market, bid, s)]
        return SimResult(
            scheme=self.schemes[s],
            bid=self.scenario.market_bids(self.markets[market])[bid],
            work_s=self.scenario.work_s,
            completed=bool(self.completed[market, bid, s]),
            completion_time=float(self.completion_time[market, bid, s]),
            cost=float(self.cost[market, bid, s]),
            n_checkpoints=int(self.n_checkpoints[market, bid, s]),
            n_kills=int(self.n_kills[market, bid, s]),
            n_self_terminations=int(self.n_self_terminations[market, bid, s]),
            work_lost_s=float(self.work_lost_s[market, bid, s]),
            runs=[],
        )

    def by_scheme(self, scheme: Scheme) -> dict[str, np.ndarray]:
        """(M, B) slices of every outcome array for one scheme."""
        s = self.scheme_index(scheme)
        return {
            "completed": self.completed[:, :, s],
            "completion_time": self.completion_time[:, :, s],
            "cost": self.cost[:, :, s],
            "n_checkpoints": self.n_checkpoints[:, :, s],
            "n_kills": self.n_kills[:, :, s],
            "n_self_terminations": self.n_self_terminations[:, :, s],
            "work_lost_s": self.work_lost_s[:, :, s],
        }

    def to_sweep_dict(self, market: int = 0) -> dict[Scheme, list[SimResult]]:
        """Legacy ``sweep_bids`` shape: ``{scheme: [result per bid]}``."""
        out: dict[Scheme, list[SimResult]] = {}
        for s, scheme in enumerate(self.schemes):
            out[scheme] = [self.cell(market, b, s) for b in range(len(self.bids))]
        return out


def fold_result_counters(tel: Telemetry, res: EngineResult) -> None:
    """Fold a finished result grid into an active collector's counters.

    The array backends accumulate kills/checkpoints *on device* inside the
    compiled program; this is where those tallies (and the scalar paths'
    equivalents) surface as telemetry, once per run — the hot loops stay
    uninstrumented.
    """
    tel.count("engine.runs")
    tel.count("engine.cells", res.n_cells)
    tel.count("engine.kills", int(res.n_kills.sum()))
    tel.count("engine.checkpoints", int(res.n_checkpoints.sum()))
    tel.count("engine.completions", int(res.completed.sum()))
    tel.count("engine.work_lost_s", float(res.work_lost_s.sum()))


def empty_result(scenario: Scenario, markets: list[MarketCell], engine: str) -> EngineResult:
    """Allocate an all-unfinished result grid for ``scenario``."""
    shape = (len(markets), len(scenario.bids), len(scenario.schemes))
    return EngineResult(
        scenario=scenario,
        engine=engine,
        markets=markets,
        bids=scenario.bids,
        schemes=scenario.schemes,
        completed=np.zeros(shape, dtype=bool),
        completion_time=np.full(shape, np.inf),
        cost=np.zeros(shape),
        n_checkpoints=np.zeros(shape, dtype=np.int64),
        n_kills=np.zeros(shape, dtype=np.int64),
        n_self_terminations=np.zeros(shape, dtype=np.int64),
        work_lost_s=np.zeros(shape),
    )


@runtime_checkable
class Engine(Protocol):
    """Anything that can evaluate a Scenario into an EngineResult."""

    name: str

    def run(self, scenario: Scenario) -> EngineResult: ...


def get_engine(name: str = "auto") -> Engine:
    """Resolve an engine by name: ``"reference"``, ``"batch"``, ``"jax"`` or
    ``"auto"``.

    ``"auto"`` is the batch backend on every platform, TPU included: it is
    held ``==`` to the reference on every scheme, and the jax backend is not
    on a TPU.  There XLA emulates float64 with about 49 mantissa bits and the
    float32 exponent range, so the grid's period times and ADAPT's survival
    tables already round on the way to the device and near-tie decisions
    flip; ``chip_smoke.py`` prints the disagreement.  ``"auto"`` moves to a
    device program once one is exact there (an integer substrate).

    Backend choice is explicit: ``"jax"`` raises :class:`ImportError` with an
    install hint when jax is missing rather than silently running on NumPy.
    """
    from repro.engine.batch import BatchEngine
    from repro.engine.reference import ReferenceEngine

    if name in ("auto", "batch"):
        return BatchEngine()
    if name == "reference":
        return ReferenceEngine()
    if name == "jax":
        from repro.engine.jax_backend import JaxEngine

        return JaxEngine()
    raise ValueError(f"unknown engine {name!r}; expected auto|batch|reference|jax")


def run(scenario: Scenario, engine: str | Engine = "auto") -> EngineResult:
    """Evaluate ``scenario`` on the selected backend."""
    eng = get_engine(engine) if isinstance(engine, str) else engine
    return eng.run(scenario)
