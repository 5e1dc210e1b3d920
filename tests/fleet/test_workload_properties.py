"""Property-based Workload invariants (hypothesis).

Requires hypothesis (in requirements-dev.txt); skipped when absent — the
deterministic coverage of the same helpers lives in test_workload.py.

The two invariants every generator and combinator must pin:

  * **sorted arrivals** — a ``Workload`` is an ordered stream; every
    constructor and ``merge()`` must emit arrivals in non-decreasing order
    (the ``FleetController`` event loop assumes it).
  * **unique ids** — job ids are the join key for attempt records and
    outcomes; ``merge()`` renumbers precisely because source streams number
    independently.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.fleet import Workload, poisson_arrivals, rate_arrivals

workloads = st.one_of(
    st.builds(
        Workload.poisson,
        n_jobs=st.integers(1, 30),
        mean_interarrival_s=st.floats(60.0, 7200.0),
        mean_work_s=st.floats(600.0, 4 * 3600.0),
        seed=st.integers(0, 2**16),
        deadline_slack=st.one_of(st.none(), st.floats(1.5, 10.0)),
    ),
    st.builds(
        Workload.batch,
        n_jobs=st.integers(1, 20),
        work_s=st.floats(600.0, 3600.0),
        arrival_s=st.floats(0.0, 86400.0),
    ),
)


def assert_invariants(w: Workload) -> None:
    arrivals = [j.arrival_s for j in w]
    assert arrivals == sorted(arrivals), "arrivals must be non-decreasing"
    ids = [j.id for j in w]
    assert len(set(ids)) == len(ids), "job ids must be unique"


@settings(max_examples=60, deadline=None)
@given(streams=st.lists(workloads, min_size=1, max_size=4))
def test_merge_invariants(streams):
    merged = streams[0].merge(*streams[1:])
    assert_invariants(merged)
    assert len(merged) == sum(len(w) for w in streams)
    # renumbering is dense 0..n-1 and job content is conserved as a multiset
    assert sorted(j.id for j in merged) == list(range(len(merged)))
    assert _content(merged) == _content(j for w in streams for j in w)


def _content(jobs) -> list:
    """Jobs as a sorted multiset of (arrival, work, deadline); a missing
    deadline sorts before any present one instead of comparing to a float."""
    return sorted(
        (j.arrival_s, j.work_s, j.deadline_s is not None, j.deadline_s or 0.0) for j in jobs
    )


@settings(max_examples=60, deadline=None)
@given(
    n=st.integers(0, 200),
    mean=st.floats(1.0, 3600.0),
    seed=st.integers(0, 2**16),
)
def test_poisson_arrivals_sorted(n, mean, seed):
    arr = poisson_arrivals(n, mean, seed=seed)
    assert arr.size == n
    assert np.all(np.diff(arr) >= 0) and np.all(arr >= 0)


@settings(max_examples=40, deadline=None)
@given(
    rates=st.lists(st.floats(0.0, 0.2), min_size=1, max_size=48),
    period=st.floats(60.0, 900.0),
    seed=st.integers(0, 2**16),
)
def test_rate_arrivals_sorted_and_bounded(rates, period, seed):
    arr = rate_arrivals(np.array(rates), period, seed=seed)
    assert np.all(np.diff(arr) >= 0)
    if arr.size:
        assert arr[0] >= 0.0 and arr[-1] < len(rates) * period
    # determinism: same inputs, same process
    assert np.array_equal(arr, rate_arrivals(np.array(rates), period, seed=seed))


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 50),
    mean_work=st.floats(600.0, 7200.0),
    seed=st.integers(0, 2**16),
)
def test_from_arrivals_invariants(n, mean_work, seed):
    w = Workload.from_arrivals(poisson_arrivals(n, 600.0, seed=seed), mean_work, seed=seed)
    assert_invariants(w)
    assert all(j.work_s >= 60.0 for j in w)
