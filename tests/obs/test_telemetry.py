"""Telemetry core: activation, span nesting, counters, events, exporters."""

import json

import pytest

from repro import obs


def test_current_is_null_when_nothing_active():
    tel = obs.current()
    assert tel is obs.NULL
    assert not tel.enabled
    # every operation is a no-op, never an error
    with tel.span("anything") as s:
        assert s is None
    tel.count("x")
    tel.event("z", 0.0)


def test_null_cannot_be_activated():
    with pytest.raises(RuntimeError):
        with obs.NULL:
            pass
    with pytest.raises(RuntimeError):
        obs.activate(obs.NULL)


def test_activation_nests_and_unwinds():
    outer = obs.Telemetry()
    inner = obs.Telemetry()
    with outer:
        assert obs.current() is outer
        with inner:
            assert obs.current() is inner
        assert obs.current() is outer
    assert obs.current() is obs.NULL


def test_reactivation_of_same_collector():
    tel = obs.Telemetry()
    with tel, obs.activate(tel):
        assert obs.current() is tel
        tel.count("k")
    assert obs.current() is obs.NULL
    assert tel.counter("k") == 1


def test_span_tree_nesting_and_self_dur():
    tel = obs.Telemetry()
    with tel.span("outer") as outer:
        with tel.span("inner", scheme="hour") as inner:
            pass
    assert tel.spans == [outer]
    assert outer.children == [inner]
    assert inner.attrs == {"scheme": "hour"}
    assert outer.dur >= inner.dur >= 0.0
    assert outer.self_dur == pytest.approx(outer.dur - inner.dur)
    assert [s.name for s in outer.find("inner")] == ["inner"]
    assert tel.find_spans("inner") == [inner]


def test_counters_gauges_events():
    tel = obs.Telemetry()
    tel.count("kills")
    tel.count("kills", 2)
    tel.event("E_ckpt", 3600.0, price=0.5)
    assert tel.counter("kills") == 3
    assert tel.counter("never") == 0
    assert not hasattr(tel, "gauge") and not hasattr(tel, "gauges")
    (ev,) = tel.events
    assert (ev.name, ev.t, ev.attrs) == ("E_ckpt", 3600.0, {"price": 0.5})
    assert ev.wall >= 0.0


def _populated():
    tel = obs.Telemetry()
    with tel.span("engine.run", engine="batch"):
        with tel.span("sim", scheme="hour"):
            pass
    tel.count("fleet.kills", 4)
    tel.event("E_terminate", 7200.0, at=7200.0)
    return tel


def test_write_jsonl(tmp_path):
    tel = _populated()
    path = tmp_path / "telemetry.jsonl"
    tel.write_jsonl(path)
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    by_type = {}
    for r in rows:
        by_type.setdefault(r["type"], []).append(r)
    assert [r["name"] for r in by_type["span"]] == ["engine.run", "sim"]
    assert by_type["span"][1]["depth"] == 1
    assert by_type["span"][1]["attrs"] == {"scheme": "hour"}
    assert by_type["event"][0]["name"] == "E_terminate"
    assert by_type["event"][0]["sim_t_s"] == 7200.0
    assert by_type["counter"] == [{"type": "counter", "name": "fleet.kills", "value": 4}]
    assert set(by_type) == {"span", "event", "counter"}


def test_write_chrome_trace(tmp_path):
    tel = _populated()
    path = tmp_path / "trace.json"
    tel.write_chrome_trace(path)
    payload = json.loads(path.read_text())
    events = payload["traceEvents"]
    slices = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    counters = [e for e in events if e["ph"] == "C"]
    assert [e["name"] for e in slices] == ["engine.run", "sim"]
    assert all(e["pid"] == 1 and e["dur"] >= 0.0 for e in slices)
    # simulation-time instants live on their own process, 1us per sim-second
    assert instants[0]["pid"] == 2 and instants[0]["ts"] == 7200.0
    assert counters[0]["args"] == {"fleet.kills": 4}


def test_summary_table_sections():
    out = _populated().summary()
    assert "engine.run" in out
    assert "fleet.kills" in out
    assert "gauge" not in out
    assert "E_terminate" in out


def test_engine_run_fills_ambient_collector():
    """An activated collector receives the engine's spans and counters."""
    from repro.core import Scheme, get_instance, synthetic_trace
    from repro.engine import Scenario, run

    tr = synthetic_trace(get_instance("m1.xlarge"), 5, seed=3)
    sc = Scenario.from_trace(tr, 3600.0, [0.36], schemes=(Scheme.HOUR,))
    with obs.Telemetry() as tel:
        res = run(sc, engine="batch")
    (root,) = tel.find_spans("engine.run")
    assert root.attrs["engine"] == "batch"
    assert tel.find_spans("sim"), "per-scheme sim spans missing"
    assert tel.counter("engine.runs") == 1
    assert tel.counter("engine.cells") == res.n_cells
    assert tel.counter("engine.kills") == int(res.n_kills.sum())
