"""Amazon EC2 spot billing rules (paper §IV), implemented exactly.

The benchmark's copy of ``repro.core.billing``, its plain reference: it imports
nothing of the program, so no later change to the program moves it.

The paper's §VII explicitly *corrects* the billing model of Yi et al.'s
simulator: each instance-hour is charged at the spot price in effect at the
**beginning** of that instance-hour (hours are relative to instance launch),
not at the last observed price.  Additional rules:

  * the final partial hour is **free** iff the instance was terminated by the
    provider (out-of-bid);
  * the final partial hour is charged as a **full hour** (at its start price)
    if the user terminates the instance forcefully — job completion counts as
    a user termination;
  * a termination exactly on an hour boundary never starts (or pays) the next
    hour.

``billing_period_s`` generalizes the 3600 s instance-hour so EXPERIMENTS.md
can ablate modern per-minute billing.
"""

from __future__ import annotations

import dataclasses
import enum
import math

from .market import HOUR, PriceTrace


class Termination(enum.Enum):
    OUT_OF_BID = "out_of_bid"  # provider kill: partial hour free
    USER = "user"  # forced by user (incl. job completion): full hour charged


@dataclasses.dataclass(frozen=True)
class BillingItem:
    hour_start: float
    price: float
    charged: bool


def bill_run(
    trace: PriceTrace,
    launch: float,
    end: float,
    termination: Termination,
    billing_period_s: float = HOUR,
) -> list[BillingItem]:
    """Itemized bill for one instance run ``[launch, end)``.

    Returns one item per started billing period.  ``charged=False`` only on
    the final partial period of an out-of-bid kill.
    """
    if end < launch:
        raise ValueError(f"end {end} < launch {launch}")
    if end == launch:
        return []
    items: list[BillingItem] = []
    n_periods = int(math.ceil((end - launch) / billing_period_s - 1e-12))
    for k in range(n_periods):
        start = launch + k * billing_period_s
        full = start + billing_period_s <= end + 1e-9
        charged = full or termination == Termination.USER
        items.append(BillingItem(hour_start=start, price=trace.price_at(start), charged=charged))
    return items


def run_cost(
    trace: PriceTrace,
    launch: float,
    end: float,
    termination: Termination,
    billing_period_s: float = HOUR,
) -> float:
    return sum(i.price for i in bill_run(trace, launch, end, termination, billing_period_s) if i.charged)
