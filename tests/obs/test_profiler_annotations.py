"""Spans on the profiler's clock: once JAX is loaded, every span of an
enabled collector is also a ``jax.profiler.TraceAnnotation`` of the same
name, so a profiler trace shows it on the host plane with its nesting and
its duration; the disabled collector emits nothing."""

import os
import pathlib
import subprocess
import sys

import pytest

from repro import obs

jax = pytest.importorskip("jax")

HOST_PLANE = "/host:CPU"


def _host_events(trace_dir) -> list[tuple[str, int, int]]:
    """Every event of the newest trace's host plane as ``(name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData

    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    (plane,) = [p for p in data.planes if p.name == HOST_PLANE]
    return [(e.name, int(e.start_ns), int(e.duration_ns)) for line in plane.lines
            for e in line.events]


def _traced(tmp_path, body):
    """Run ``body()`` under the profiler; returns the host plane's events."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # only annotations, not every call
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(tmp_path)


def _span_rows(tel):
    """``(depth, name, t0_s, dur_s)`` of every span, depth first."""

    def walk(spans, depth):
        for s in spans:
            yield depth, s.name, s.t0, s.dur
            yield from walk(s.children, depth + 1)

    return list(walk(tel.spans, 0))


def _event_rows(events, names):
    """``(depth, name, start_ns, dur_ns)`` of the events named in ``names``,
    nested by containment, in start order (a parent before its children)."""
    chosen = sorted((s, -d, n) for n, s, d in events if n in names)
    rows, open_ends = [], []
    for s, neg_d, n in chosen:
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        rows.append((len(open_ends), n, s, -neg_d))
        open_ends.append(s - neg_d)
    return rows


def _scenario():
    from repro.core import get_instance
    from repro.engine import BID_LIMITED_SCHEMES, Scenario

    its = [get_instance("m1.xlarge"), get_instance("c1.medium")]
    return Scenario.grid(work_s=6 * 3600.0, bids=(0.5, 0.6), instances=its,
                         schemes=BID_LIMITED_SCHEMES, horizon_days=8, seeds=[0, 1],
                         bid_fractions=True)


def test_every_span_is_a_host_event_with_its_name_nesting_and_duration(tmp_path):
    from repro.engine import run

    run(_scenario(), engine="jax")  # compile outside the trace
    tel = obs.Telemetry()

    def body():
        with tel, tel.span("test.root"):
            run(_scenario(), engine="jax")

    events = _traced(tmp_path, body)
    spans = _span_rows(tel)
    assert {"materialize", "sim.inputs", "sim.h2d", "sim.device", "sim.fetch", "bill"} <= {
        name for _, name, _, _ in spans}
    got = _event_rows(events, {name for _, name, _, _ in spans})
    assert [(d, n) for d, n, _, _ in got] == [(d, n) for d, n, _, _ in spans]
    root_t0, root_start = spans[0][2], got[0][2]
    for (_, name, t0, dur), (_, _, start_ns, dur_ns) in zip(spans, got):
        assert abs(dur_ns * 1e-9 - dur) < 1e-3, name
        # the same clock: offsets from the root agree too
        assert abs((start_ns - root_start) * 1e-9 - (t0 - root_t0)) < 1e-3, name


def test_disabled_collector_emits_no_event(tmp_path):
    assert obs.current() is obs.NULL

    def body():
        with obs.current().span("probe.off"):
            pass
        with obs.NULL.span("probe.null"):
            pass
        with obs.Telemetry().span("probe.on"):
            pass

    names = [n for n, _, _ in _traced(tmp_path, body)]
    assert "probe.on" in names  # the control: an enabled collector's span
    assert "probe.off" not in names and "probe.null" not in names


def test_annotation_closes_when_the_span_raises(tmp_path):
    tel = obs.Telemetry()

    def body():
        with pytest.raises(ValueError):
            with tel.span("probe.raise"):
                raise ValueError("inside the span")
        with tel.span("probe.after"):
            pass

    rows = _event_rows(_traced(tmp_path, body), {"probe.raise", "probe.after"})
    # the raising span closed: the next one is a sibling, not its child
    assert [(d, n) for d, n, _, _ in rows] == [(0, "probe.raise"), (0, "probe.after")]
    assert not tel._stack


def test_obs_does_not_import_jax():
    code = "import sys, repro.obs as o; o.Telemetry().span('x').__enter__(); " \
           "sys.exit('jax' in sys.modules)"
    import repro

    src = pathlib.Path(repro.__file__).resolve().parents[1]
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          timeout=60, env=dict(os.environ, PYTHONPATH=str(src)))
    assert proc.returncode == 0, proc.stderr
