"""Reduce a profiler trace to device busy time, program time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load` turns it into plain events, ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}``, which :func:`reduce` reads; a trace saved in that form
(:func:`save`) reduces the same way without the profiler, which is how the
tests check this code on a recorded trace.

On a TPU the device plane is ``/device:TPU:<n>``: its ``XLA Modules`` line
holds one event per program run (named ``jit_<function>(<id>)``) and its
``XLA Ops`` line one per operation.  Busy time is the union of the operation
intervals (the module intervals where a plane has no op line); host spans
come from the ``TraceAnnotation`` events of the host plane, on the same
clock.
"""

from __future__ import annotations

import bisect
import gzip
import json
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def load(trace_dir: pathlib.Path) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain events: every
    device plane's module and op lines, and every event of the host plane."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    out: dict = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != "/host:CPU":
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
        out[plane.name] = lines
    return out


def save(events: dict, path: pathlib.Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: pathlib.Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def program_name(event_name: str) -> str:
    """``jit_fn(1234)`` -> ``jit_fn``: a module event's program name."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.4 = f32[10496]{...} fusion(...)`` -> ``fusion.4``: an op
    event's instruction name (TPU op events carry the whole instruction)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, merged ``[start, end)`` intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def device_planes(events: dict) -> list[str]:
    return sorted(p for p in events if DEVICE_PLANE.match(p))


def annotations(events: dict, prefix: str) -> list[tuple[str, int, int]]:
    """Host events whose name starts with ``prefix``, as ``(name, start, end)``."""
    host = events.get("/host:CPU", {})
    out = [(n, s, s + d) for line in host.values() for n, s, d in line if n.startswith(prefix)]
    return sorted(out, key=lambda t: t[1])


def reduce(events: dict, window: tuple[int, int], host_spans=(), top: int = 10) -> dict:
    """Numbers of one traced window ``[start_ns, end_ns)``.

    Returns ``busy_s`` (union of device intervals in the window, averaged
    over the device planes), ``window_s``, ``program_s`` (device seconds per
    program name, summed over planes), ``device_ops`` (the ``top`` operations
    by device time) and ``idle_gaps``: device idle time in the window by the
    innermost host span open at each idle instant, from ``host_spans``, a
    sequence of ``(name, start_ns, end_ns)`` on the trace's clock (``host``
    where none is open).
    """
    w0, w1 = window
    planes = device_planes(events)
    busy_total = 0
    program_s: dict[str, float] = {}
    op_s: dict[str, float] = {}
    idle_by: dict[str, float] = {}
    for plane in planes:
        lines = events[plane]
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in ops if s < w1 and s + d > w0]
        busy = union(clipped)
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in lines.get(MODULE_LINE, []):
            if s < w1 and s + d > w0:
                key = program_name(name)
                program_s[key] = program_s.get(key, 0.0) + (min(s + d, w1) - max(s, w0)) * 1e-9
        for name, s, d in lines.get(OP_LINE, []):
            if s < w1 and s + d > w0:
                key = op_name(name)
                op_s[key] = op_s.get(key, 0.0) + (min(s + d, w1) - max(s, w0)) * 1e-9
        for name, secs in _idle_by_span(busy, (w0, w1), host_spans).items():
            idle_by[name] = idle_by.get(name, 0.0) + secs / len(planes)
    n = max(len(planes), 1)
    return {
        "busy_s": busy_total * 1e-9 / n,
        "window_s": (w1 - w0) * 1e-9,
        "program_s": program_s,
        "device_ops": sorted(([k, v] for k, v in op_s.items()), key=lambda t: -t[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle_by.items()), key=lambda t: -t[1])[:top],
    }


def _idle_by_span(busy, window, host_spans) -> dict[str, float]:
    """Idle seconds between the busy intervals, split by the innermost host
    span open over each piece (``host`` where none is)."""
    w0, w1 = window
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    # the span boundaries cut the timeline into pieces, each with one
    # innermost open span: name them once, then walk each gap over them
    cuts = sorted({x for _, s, e in host_spans for x in (s, e)} | {w0, w1})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e in host_spans if s <= mid < e]
        names.append(min(open_)[1] if open_ else "host")
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        k = max(bisect.bisect_right(cuts, g0) - 1, 0)
        while k < len(names) and cuts[k] < g1:
            piece = min(g1, cuts[k + 1]) - max(g0, cuts[k])
            if piece > 0:
                out[names[k]] = out.get(names[k], 0.0) + piece * 1e-9
            k += 1
    return out
