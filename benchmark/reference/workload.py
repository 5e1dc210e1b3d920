"""The fleet's job stream: Poisson arrivals with lognormal work sizes.

The benchmark's plain reference of ``repro.fleet.workload``'s
``Workload.poisson``: it imports nothing of the program.  Work is in
reference-ECU seconds (the 8-ECU m1.xlarge is the reference); a job of
``work_s`` takes ``work_s * 8 / ECU`` wall seconds on a type.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class SLA:
    """The least compute throughput and the operating system a job admits."""

    min_compute_units: float = 0.0
    os: str | None = None

    def admits(self, it) -> bool:
        if it.compute_units < self.min_compute_units:
            return False
        return self.os is None or it.os == self.os


@dataclasses.dataclass(frozen=True)
class Job:
    id: int
    arrival_s: float
    work_s: float  # reference-ECU seconds
    deadline_s: float | None
    sla: SLA


def poisson_stream(n_jobs: int, mean_interarrival_s: float, mean_work_s: float, seed: int,
                   sla: SLA, work_sigma: float = 0.5,
                   deadline_slack: float | None = None) -> list[Job]:
    """``n_jobs`` Poisson arrivals (exponential gaps) with lognormal work of
    mean ``mean_work_s`` and log-sd ``work_sigma``, at least 60 s each, drawn
    from ``default_rng(seed)``: the gaps first, then the sizes.  With
    ``deadline_slack`` a job's deadline is ``arrival + slack * work``."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(mean_interarrival_s, n_jobs))
    mu = np.log(mean_work_s) - 0.5 * work_sigma**2  # E[e^X] = e^(mu + sigma^2 / 2)
    works = np.maximum(rng.lognormal(mu, work_sigma, n_jobs), 60.0)
    jobs = []
    for i in range(n_jobs):
        a, w = float(arrivals[i]), float(works[i])
        d = a + deadline_slack * w if deadline_slack is not None else None
        jobs.append(Job(i, a, w, d, sla))
    return jobs
