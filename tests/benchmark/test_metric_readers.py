"""The readers of the program's own phase spans: trace generation
(``market_ms``) and the sweep's host↔device round trip (``sweep_*_ms``,
``fetch_gb_s``).  Each returns the mean per study it should, returns None
where its spans are absent (as in a program that lacks them), and leaves the
readers of the older spans reading what they read before."""

from __future__ import annotations

import pytest
from conftest import tiny_study

from benchmark import harness

NEW = ["market_ms", "sweep_inputs_ms", "sweep_h2d_ms", "sweep_wait_ms", "sweep_fetch_ms",
       "fetch_gb_s"]
OLD = ["materialize_ms", "acc_ms", "grid_ms", "bill_ms"]
#: the sweep's phases, in order, and the reader of each
PHASES = {"sim.inputs": "sweep_inputs_ms", "sim.h2d": "sweep_h2d_ms",
          "sim.device": "sweep_wait_ms", "sim.fetch": "sweep_fetch_ms"}


def _study(i: int, new_spans: bool) -> harness.StudyRecord:
    """A hand-made study: seconds that differ per study and per phase; with
    ``new_spans``, the ``materialize`` span, the scan's phases and the
    fetched bytes as the program now records them."""
    from repro.obs import Span, Telemetry

    k = 1.0 + i
    phases = [Span(name, t0=0.0, dur=k * d) for name, d in
              zip(PHASES, (0.010, 0.020, 0.120, 0.030))]
    scan = Span("sim", 0.0, k * 0.190, {"impl": "scan"}, phases if new_spans else [])
    acc = Span("sim", 0.0, k * 0.500, {"scheme": "acc", "impl": "ref"},
               [Span("bill", 0.0, k * 0.040, {"scheme": "acc"})])
    bills = [Span("bill", 0.0, k * 0.090, {"scheme": s}) for s in ("none", "hour")]
    run = Span("engine.run", 0.0, k * 1.0, {"engine": "jax"},
               [Span("grid", 0.0, k * 0.180), acc, scan, *bills])
    children = [Span("materialize", 0.0, k * 0.100)] if new_spans else []
    root = Span("bench.study", 0.0, k * 1.15, children=children + [run])
    tel = Telemetry()
    tel.spans = [root]
    if new_spans:
        tel.count("sweep.d2h_bytes", 93.4e6)
    return harness.StudyRecord(i, root.dur, tel, root, f"bench.study {i}", 0)


def _run(new_spans: bool) -> harness.RunData:
    return harness.RunData([_study(i, new_spans) for i in range(3)], None, {}, {})


@pytest.mark.parametrize("name, expected", [
    ("market_ms", 100.0 * 2),  # the studies' k: 1, 2 and 3, mean 2
    ("sweep_inputs_ms", 10.0 * 2),
    ("sweep_h2d_ms", 20.0 * 2),
    ("sweep_wait_ms", 120.0 * 2),
    ("sweep_fetch_ms", 30.0 * 2),
    ("fetch_gb_s", 93.4e6 / 0.060 / 1e9),
])
def test_new_reader_reads_its_mean(name, expected):
    assert harness.load_reader(name).read(_run(True)) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_is_silent_without_its_spans(name):
    assert harness.load_reader(name).read(_run(False)) is None


@pytest.mark.parametrize("name", OLD)
def test_old_reader_reads_the_same_with_the_new_spans(name):
    reader = harness.load_reader(name)
    before, after = reader.read(_run(False)), reader.read(_run(True))
    assert before is not None and after == pytest.approx(before)


@pytest.mark.parametrize("workload", ["catalog.study", "catalog.acc"])
def test_readers_on_a_tiny_run(tiny_bench, workload):
    _, study = tiny_study(tiny_bench, workload)
    compiles = harness.CompileCounter()
    harness.one_study(study, -1, compiles, annotate=False)
    records = [harness.one_study(study, i, compiles, annotate=False)[0] for i in range(2)]
    run = harness.RunData(records, None, {}, {})
    got = {name: harness.load_reader(name).read(run) for name in NEW + ["materialize_ms"]}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["market_ms"] <= got["materialize_ms"]
    scan_ms = 1e3 * sum(s.dur for r in records for s in r.tel.find_spans("sim")
                        if s.attrs.get("impl") == "scan") / len(records)
    assert sum(got[PHASES[p]] for p in PHASES) <= scan_ms
    moved = sum(r.tel.counter("sweep.d2h_bytes") for r in records) / len(records)
    assert got["fetch_gb_s"] == pytest.approx(moved / got["sweep_fetch_ms"] / 1e6)
