"""Per-layer metric readers, one module per metric, found by the metric's name.

Each ``<name>.py`` has ``read(run) -> float | None`` over a
:class:`benchmark.harness.RunData`: the window's studies with the program's
spans, the reduced trace and the shapes.  A reader that finds nothing to read
returns None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

#: module (program) name of the sweep program in the trace
SWEEP_PROGRAM = "jit_fn"


def spans(record, name: str, **attrs) -> list:
    """One study's spans named ``name`` whose attributes include ``attrs``."""
    return [s for s in record.tel.find_spans(name)
            if all(s.attrs.get(k) == v for k, v in attrs.items())]


def mean_ms(run, per_study) -> float | None:
    """Mean over the window's studies of ``per_study(record)`` seconds, in
    ms; None when any study has nothing to read."""
    values = [per_study(r) for r in run.studies]
    if not values or any(v is None for v in values):
        return None
    return 1e3 * sum(values) / len(values)


def total_s(run, name: str, **attrs):
    """A per-study reader: the summed duration of its ``name`` spans, None
    when it has none."""

    def per_study(record):
        found = spans(record, name, **attrs)
        return sum(s.dur for s in found) if found else None

    return per_study


def program_s(run, program: str) -> float | None:
    """Device seconds of ``program`` over the traced window, None without it."""
    if run.trace is None:
        return None
    return run.trace["program_s"].get(program) or None


def roofline_pct(run, least_bytes: float, program: str) -> float | None:
    """Least bytes over the peak bandwidth, as a share of the program's
    device time in the window."""
    secs = program_s(run, program)
    if secs is None or not least_bytes:
        return None
    return 100.0 * least_bytes / run.peaks["hbm_bytes_per_s"] / secs
