"""Least bytes of the fleet's EET scoring program, from its own counters.

Each call of ``repro.kernels.fleet_step``'s jitted ``eet_scores_jax`` reads
three float64 ``(lanes, types)`` operands and a boolean mask and writes one
float64 score per entry, with lanes padded to their bucket; the program
counts those entries in ``fleet_step.cells``.  A handful of operations per
entry, so bytes set the bound, as for the sweep (:mod:`benchmark.roofline`).
"""

from __future__ import annotations

from benchmark.roofline import BOOL, F64

#: module (program) name of the EET scoring program in the trace
EET_PROGRAM = "jit_eet_scores_jax"


def eet_bytes(cells: int) -> int:
    """Least bytes for ``cells`` scored entries: ``p_fail``, ``wasted`` and
    ``w_scaled`` read (8 B each), ``avail`` read (1 B), the score written
    (8 B)."""
    return cells * (3 * F64 + BOOL + F64)
