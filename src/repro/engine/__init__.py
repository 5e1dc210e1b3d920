"""One Scenario/Engine API: the declarative simulation surface.

Everything the repo simulates — §VII bid sweeps, fleet studies, SpotTrainer
markets — is described by a frozen scenario object and evaluated by an
interchangeable engine backend:

  * :class:`Scenario` / :class:`FleetScenario` — what to simulate
    (market, workload, schemes, bid grid, params, seeds), never how.
  * :class:`ReferenceEngine` — the scalar event loop, cell by cell;
    semantically canonical.
  * :class:`BatchEngine` — structure-of-arrays NumPy lockstep over the
    whole (type × bid × seed) grid for every bid-limited scheme — ADAPT
    included, its hazard decision precomputed into binned survival tables —
    bit-identical to the reference (see :mod:`repro.engine.parity`); only
    ACC cells fall back to the scalar path.
  * :class:`JaxEngine` — the fused spot-sweep program
    (:mod:`repro.kernels.spot_sweep`): every scheme in **one** jit-compiled
    ``lax.scan``/``lax.while_loop`` program on ``jax.numpy`` with x64,
    billing inputs accumulated on-device; explicit opt-in via
    ``engine="jax"``, the same exact-parity contract on CPU (a TPU emulates
    float64 and is not exact), >= batch throughput there (CI-gated).
  * :class:`PallasEngine` — the same step as a fused Pallas TPU kernel, in
    interpreter mode only (``PallasEngine(interpret=True)``); the float64
    kernel does not compile natively, so a native one raises.
  * :func:`run` / :func:`run_fleet` — the one-call entry points.

This is the *only* sweep surface: the long-deprecated shims
(``repro.core.simulator.sweep_bids``, ``repro.fleet.sweep.run_sweep``) have
been removed — see docs/engine.md for the migration table.  Scenarios can
also declare a capacity-constrained market (``capacity`` / ``demand`` knobs,
:mod:`repro.market`): every backend then simulates on the auction-cleared
price path, preempting replicas the clearing price outbids.
"""

from repro.engine.base import (
    PARITY_FIELDS,
    Engine,
    EngineResult,
    get_engine,
    run,
)
from repro.engine.batch import BatchEngine
from repro.engine.fleetgrid import FleetGridResult, policy_registry, resolve_policies, run_fleet
from repro.engine.jax_backend import JaxEngine, PallasEngine, have_jax
from repro.engine.parity import (
    CellMismatch,
    ParityReport,
    assert_parity,
    compare_engines,
)
from repro.engine.reference import ReferenceEngine
from repro.engine.scenario import (
    BATCHED_SCHEMES,
    BID_LIMITED_SCHEMES,
    FleetScenario,
    MarketCell,
    Scenario,
)

__all__ = [
    "BATCHED_SCHEMES",
    "BID_LIMITED_SCHEMES",
    "PARITY_FIELDS",
    "BatchEngine",
    "JaxEngine",
    "PallasEngine",
    "have_jax",
    "CellMismatch",
    "Engine",
    "EngineResult",
    "FleetGridResult",
    "FleetScenario",
    "MarketCell",
    "ParityReport",
    "ReferenceEngine",
    "Scenario",
    "assert_parity",
    "compare_engines",
    "get_engine",
    "policy_registry",
    "resolve_policies",
    "run",
    "run_fleet",
]
