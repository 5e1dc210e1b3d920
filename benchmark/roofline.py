"""Least bytes each device program must move, from its shapes, and the
table of peaks by ``device_kind``.

A roofline share is the least time the chip could take, the least bytes over
the peak bandwidth, divided by the program's device time from the trace.
The sweep does a handful of float64 operations per byte, far below what
would make it bound by compute, so bytes set the bound.
"""

from __future__ import annotations

import json
import pathlib

F64 = I64 = 8
BOOL = 1

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def sweep_bytes(P: int, C: int, E: int, schemes) -> int:
    """The sweep scan over ``P`` padded periods and ``C`` cells: each input
    read once (period starts and ends, the valid mask, the horizons, and for
    EDGE the ``E`` rising edges with their per-cell base, count and the
    per-period edge cursor), and per scheme the run records (exists, end,
    user-terminated per period) and the finals the host reads (done,
    completion time, checkpoints, work lost, kills) written once."""
    read = P * C * (2 * F64 + BOOL) + C * F64
    if "edge" in schemes:
        read += E * F64 + C * 2 * I64 + P * C * I64
    per_scheme = P * C * (BOOL + F64 + BOOL) + C * (BOOL + F64 + I64 + F64 + I64)
    return read + len(schemes) * per_scheme


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
