"""Study kinds: how a configuration of each kind becomes a timed study.

``benchmark/kinds/<kind>.py`` holds a ``Study`` (built from a configuration,
a traffic mix and the run's seed; it runs one study, says whether the study
did its own work, keeps what the comparison needs, and compares it with the
plain reference) and the ``LIMITS`` of its compared numbers.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def seed_rng(seed: int) -> np.random.Generator:
    """The run's generator of orders and samples; any whole number seeds it."""
    return np.random.default_rng(abs(int(seed)))


def held_in(trace, precision):
    """The trace with its times and prices rounded to ``precision``: the
    control's inputs (segments last at least 30 s, far above float32's
    spacing over 30 days, so no two boundaries merge)."""
    return dataclasses.replace(trace, times=trace.times.astype(precision).astype(np.float64),
                               prices=trace.prices.astype(precision).astype(np.float64))
