"""EngineResult.timings: the typed per-phase breakdown every backend reports.

`engine_bench --profile` renders these; the contract is that **all** backends
populate a :class:`repro.engine.base.PhaseTimings` built from the run's span
tree — the NumPy batch driver with a per-scheme sim/billing split, the fused
device backends with one `sim_s` covering all schemes plus per-scheme
billing, the scalar reference engine with `scalar_s`.
"""

import pytest

from repro.core import Scheme, get_instance, synthetic_trace
from repro.engine import BID_LIMITED_SCHEMES, Scenario, get_engine
from repro.engine.base import PhaseTimings

IT = get_instance("m1.xlarge")


def _scenario(schemes=BID_LIMITED_SCHEMES):
    tr = synthetic_trace(IT, 10, seed=2)
    return Scenario.from_trace(tr, 6 * 3600.0, [0.36, 0.37], schemes=schemes)


def _assert_phase_times(timings, engine, schemes, sim_per_scheme: bool):
    assert isinstance(timings, PhaseTimings)
    assert timings.engine == engine
    assert timings.total_s >= 0.0
    assert timings.grid_s >= 0.0
    assert set(timings.per_scheme) == {s.value for s in schemes}
    for phases in timings.per_scheme.values():
        assert phases.bill_s >= 0.0
        if sim_per_scheme:
            assert phases.sim_s >= 0.0
    if not sim_per_scheme:  # fused backends time the one-compile sim phase
        assert timings.sim_s >= 0.0
    assert timings.sim_total_s >= 0.0


def test_batch_timings_have_sim_and_billing_phases():
    res = get_engine("batch").run(_scenario())
    _assert_phase_times(res.timings, "batch", BID_LIMITED_SCHEMES, sim_per_scheme=True)
    assert res.timings.impl is None  # NumPy driver: no device impl label


def test_batch_timings_cover_every_scheme_including_acc():
    res = get_engine("batch").run(_scenario(schemes=tuple(Scheme)))
    _assert_phase_times(res.timings, "batch", tuple(Scheme), sim_per_scheme=True)
    assert res.timings.scalar_s == 0.0  # ACC is batched: no scalar phase at all


def test_jax_timings_have_fused_sim_and_per_scheme_billing():
    pytest.importorskip("jax")
    res = get_engine("jax").run(_scenario())
    _assert_phase_times(res.timings, "jax", BID_LIMITED_SCHEMES, sim_per_scheme=False)
    assert res.timings.impl == "scan"


def test_jax_sim_s_keeps_the_sweep_phases():
    """``sim_s`` leaves out only billing: the sweep's ``sim.*`` phases are
    simulation time."""
    pytest.importorskip("jax")
    from repro import obs

    with obs.Telemetry() as tel:
        res = get_engine("jax").run(_scenario())
    phases = [c for s in tel.find_spans("sim") for c in s.children if c.name.startswith("sim.")]
    assert {c.name for c in phases} == {"sim.inputs", "sim.h2d", "sim.device", "sim.fetch"}
    assert res.timings.sim_s >= sum(c.dur for c in phases) > 0


def test_reference_engine_reports_scalar_phase():
    res = get_engine("reference").run(_scenario(schemes=(Scheme.HOUR,)))
    assert isinstance(res.timings, PhaseTimings)  # every backend populates it
    assert res.timings.engine == "reference"
    assert res.timings.scalar_s > 0.0  # the whole run is the scalar phase
    assert res.timings.per_scheme == {}
    assert res.wall_s >= 0.0


def test_phase_timings_asdict_is_json_ready():
    import json

    res = get_engine("batch").run(_scenario())
    d = res.timings.asdict()
    json.dumps(d)  # must not raise
    assert d["engine"] == "batch"
    assert set(d["per_scheme"]) == {s.value for s in BID_LIMITED_SCHEMES}
