"""Placement policies of the fleet: which type (and bid) serves a job.

The benchmark's plain reference of ``repro.fleet.policies`` and of the
paper's Algorithm 1 (``repro.core.provision``), with fixed-margin bids only:
it imports nothing of the program.

  * ``algorithm1``: A_bid is the least on-demand price over the feasible
    types (Eq. 7); the type minimises the Expected Execution Time of Eq. 8
    under that one bid, ties going to the cheaper on-demand price.
  * ``cost_greedy``: the least on-demand $/ECU that the current spot price
    admits at ``margin x on-demand``, else the least $/ECU.
  * ``eet_greedy``: the least Eq. 8 time under each type's own margin bid
    that the current spot price admits, else the least overall.
  * ``diversified``: ``k`` replicas down the same ranking, in distinct
    regions first, then distinct hardware, then any.

Eq. 8 reads the failure pdf of the type's price *history* at the bid; a type
whose history never falls to the bid scores infinity (its pdf would be all
censored mass, which Eq. 8 would read as "never fails").
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from .schemes import FailurePdf

#: work is in seconds of the 8-ECU reference type
REFERENCE_ECU = 8.0


def expected_execution_time(pdf: FailurePdf, work_s: float, recovery_s: float) -> float:
    """Eq. 8, in seconds: ``(w * P(success) + sum_{k<w} (k + r) f(k)) /
    P(success)`` over the pdf's bins below the work's bin count."""
    w_bins = max(1, int(math.ceil(work_s / pdf.bin_s)))
    k = np.arange(len(pdf.pdf))
    fail_before = pdf.pdf[:w_bins] if w_bins <= len(pdf.pdf) else pdf.pdf
    p_fail = float(np.sum(fail_before))
    p_succeed = 1.0 - p_fail  # censored mass counts as success
    if p_succeed <= 0.0:
        return math.inf
    wasted = float(np.sum((k[: len(fail_before)] * pdf.bin_s + recovery_s) * fail_before))
    return (work_s * p_succeed + wasted) / p_succeed


@dataclasses.dataclass
class Context:
    """What a policy observes: price histories (for the pdfs, cached per
    type and bid), the recovery time, the bid margin and the spot prices at
    the moment of placement."""

    histories: dict
    recovery_s: float
    margin: float
    prices_now: dict = dataclasses.field(default_factory=dict)
    pdfs: dict = dataclasses.field(default_factory=dict)

    def pdf(self, name: str, bid: float) -> FailurePdf:
        key = (name, round(bid, 6))
        if key not in self.pdfs:
            self.pdfs[key] = FailurePdf.from_trace(self.histories[name], bid)
        return self.pdfs[key]

    def eet(self, it, bid: float, work_s: float) -> float:
        hist = self.histories[it.name]
        if not (hist.prices <= bid).any():
            return math.inf
        w_scaled = work_s * (REFERENCE_ECU / it.compute_units)
        return expected_execution_time(self.pdf(it.name, bid), w_scaled, self.recovery_s)

    def ranked(self, work_s: float, feasible) -> list[tuple[float, object, float]]:
        """``(eet, type, bid)`` at margin bids, by Eq. 8 time, then on-demand
        price, then name."""
        out = []
        for it in feasible:
            bid = self.margin * it.on_demand
            out.append((self.eet(it, bid, work_s), it, bid))
        out.sort(key=lambda e: (e[0], e[1].on_demand, e[1].name))
        return out


def algorithm1(work_s, feasible, ctx: Context, k=None):
    a_bid = min(it.on_demand for it in feasible)  # Eq. 7
    best = None
    for it in feasible:
        eet = ctx.eet(it, a_bid, work_s)
        if best is None or (eet, it.on_demand) < (best[0], best[1]):
            best = (eet, it.on_demand, it)
    return [(best[2], a_bid)]


def cost_greedy(work_s, feasible, ctx: Context, k=None):
    ranked = sorted(feasible, key=lambda it: it.on_demand / it.compute_units)
    for it in ranked:
        bid = ctx.margin * it.on_demand
        if ctx.prices_now[it.name] <= bid:
            return [(it, bid)]
    return [(ranked[0], ctx.margin * ranked[0].on_demand)]


def eet_greedy(work_s, feasible, ctx: Context, k=None):
    ranked = ctx.ranked(work_s, feasible)
    for _, it, bid in ranked:
        if ctx.prices_now[it.name] <= bid:
            return [(it, bid)]
    return [(ranked[0][1], ranked[0][2])]


def diversified(n_replicas: int):
    def place(work_s, feasible, ctx: Context, k=None):
        k = n_replicas if k is None else k
        ranked = ctx.ranked(work_s, feasible)
        out: list = []
        regions: set = set()
        hardware: set = set()
        for distinct in ("region", "hardware", None):
            for _, it, bid in ranked:
                if len(out) >= k:
                    return out
                if any(p[0].name == it.name for p in out):
                    continue
                if distinct == "region" and it.region in regions:
                    continue
                if distinct == "hardware" and it.hardware in hardware:
                    continue
                out.append((it, bid))
                regions.add(it.region)
                hardware.add(it.hardware)
        return out

    return place


def policy(name: str, n_replicas: int):
    """The placement function named ``name``: ``place(work_s, feasible, ctx,
    k=None) -> [(type, bid), ...]``."""
    if name == "diversified":
        return diversified(n_replicas)
    return {"algorithm1": algorithm1, "cost_greedy": cost_greedy,
            "eet_greedy": eet_greedy}[name]
