"""The reduction from a profiler trace to busy time, program time and idle
gaps, on a hand-made trace and on a small trace recorded on a TPU v5e (one
``catalog.study`` study: its sweep program's module event, the first 1,500
and last 300 of its 35,099 op events, and the study's annotation)."""

from __future__ import annotations

import numpy as np
import pytest
from conftest import ROOT

from benchmark import trace_reduce

RECORDED = ROOT / "tests" / "benchmark" / "tpu_v5e_catalog_study.json.gz"
DEV = "/device:TPU:0"


def _hand_made():
    # device busy [10, 30) and [25, 40) (overlapping ops) and [60, 70), in ns;
    # window [0, 100); host spans: study [0, 100), grid [0, 20), bill [50, 90)
    events = {
        DEV: {"XLA Modules": [["jit_fn(1)", 10, 30], ["jit_eet(2)", 60, 10]],
              "XLA Ops": [["%while.1 = f32[8] while(...)", 10, 20], ["%fusion.2 = f32[8]", 25, 15],
                          ["%fusion.3 = f32[8]", 60, 10]]},
        "/host:CPU": {"python3": [["bench.study 0", 0, 100]]},
    }
    spans = [("bench.study", 0, 100), ("grid", 0, 20), ("bill", 50, 90)]
    return events, spans


def test_hand_made_trace():
    events, spans = _hand_made()
    r = trace_reduce.reduce(events, (0, 100), spans)
    assert r["busy_s"] == pytest.approx(40e-9)  # [10, 40) and [60, 70)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["program_s"] == pytest.approx({"jit_fn": 30e-9, "jit_eet": 10e-9})
    assert [n for n, _ in r["device_ops"]] == ["while.1", "fusion.2", "fusion.3"]
    idle = dict(r["idle_gaps"])
    # idle [0, 10) in grid; [40, 50) in the study; [50, 60) and [70, 90) in
    # bill; [90, 100) in the study
    assert idle == pytest.approx({"grid": 10e-9, "bench.study": 20e-9, "bill": 30e-9})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_window_clips_events_and_no_span_is_host():
    events, _ = _hand_made()
    r = trace_reduce.reduce(events, (20, 65), [])
    assert r["busy_s"] == pytest.approx(25e-9)  # [20, 40) and [60, 65)
    assert r["program_s"] == pytest.approx({"jit_fn": 20e-9, "jit_eet": 5e-9})
    assert dict(r["idle_gaps"]) == pytest.approx({"host": 20e-9})


def test_annotations_and_names():
    events, _ = _hand_made()
    assert trace_reduce.annotations(events, "bench.study ") == [("bench.study 0", 0, 100)]
    assert trace_reduce.program_name("jit_fn(16317285510985662932)") == "jit_fn"
    assert trace_reduce.op_name("%fusion.5 = f32[10496]{0} fusion(f32[98953]{0} %x)") == "fusion.5"


def test_recorded_tpu_trace():
    events = trace_reduce.read_saved(RECORDED)
    (name, w0, w1), = trace_reduce.annotations(events, "bench.study ")
    (module, m0, mdur), = events[DEV]["XLA Modules"]
    assert name == "bench.study 0" and w0 < m0 < m0 + mdur < w1  # one clock for host and device
    # a span over the device program and the study around it
    spans = [("bench.study", w0, w1), ("sim", m0 - 1000, m0 + mdur + 1000)]
    r = trace_reduce.reduce(events, (w0, w1), spans)
    # busy: the union of the op intervals, counted here on a 1 us grid
    grid = np.zeros((w1 - w0) // 1000 + 1, dtype=bool)
    for _, s, d in events[DEV]["XLA Ops"]:
        grid[(s - w0) // 1000:(s + d - w0 + 999) // 1000] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert r["program_s"] == {"jit_fn": mdur * 1e-9}
    assert r["device_ops"][0][0] == "while.148"
    idle = dict(r["idle_gaps"])
    assert set(idle) == {"bench.study", "sim"}
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert idle["sim"] == pytest.approx(2000e-9 + mdur * 1e-9 - r["busy_s"], rel=1e-6)
