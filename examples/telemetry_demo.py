"""Telemetry demo: one collector across engine, market, and fleet layers.

Activates a single :class:`repro.obs.Telemetry` collector, runs a batched
engine sweep and a contended fleet replay under it, then exports

  * ``/tmp/repro_trace.json`` — Chrome trace_event JSON.  Open
    ``chrome://tracing`` (or https://ui.perfetto.dev) and load the file:
    wall-clock spans land on the "wall clock" track, simulation-time
    events (launches, kills, checkpoints) on "simulation (1us = 1s)".
  * ``/tmp/repro_telemetry.jsonl`` — one JSON object per span / event /
    counter, for ad-hoc analysis.
  * a plain-text summary on stdout via :meth:`Telemetry.summary`.

Run:  PYTHONPATH=src python examples/telemetry_demo.py
"""

from repro import configure_logging, obs
from repro.core import HOUR, Scheme, constant_trace, get_instance, synthetic_trace
from repro.engine import BID_LIMITED_SCHEMES, Scenario, run
from repro.fleet import ClearingRebid, CostGreedyPolicy, FleetController, Workload

log = configure_logging()

tel = obs.Telemetry()

# --- 1. an engine sweep: spans for grid build, per-scheme sim, billing ------
it = get_instance("m1.xlarge", region="us-east-1")
trace = synthetic_trace(it, horizon_days=10, seed=7)
scenario = Scenario.from_trace(trace, 6 * 3600.0, [0.36, 0.40], schemes=BID_LIMITED_SCHEMES)
with tel:
    run(scenario, engine="batch")

# --- 2. a contended fleet: kills, migrations, re-clears as sim-time events --
ctl = FleetController(
    [it],
    {it.name: constant_trace(0.36, 60 * 3600.0)},
    CostGreedyPolicy(),
    scheme=Scheme.HOUR,
    bid_margin=0.56,
    capacity=4,
    bid_policy=ClearingRebid(margin=0.56, markup=0.10),
)
with tel:
    ctl.run(Workload.from_sizes([6.0] * 4, interarrival_s=0.5 * HOUR))

# --- 3. export -------------------------------------------------------------
tel.write_chrome_trace("/tmp/repro_trace.json")
tel.write_jsonl("/tmp/repro_telemetry.jsonl")
log.info(tel.summary())
log.info("")
log.info("wrote /tmp/repro_trace.json       (load in chrome://tracing or ui.perfetto.dev)")
log.info("wrote /tmp/repro_telemetry.jsonl  (one JSON object per span/event/counter)")
