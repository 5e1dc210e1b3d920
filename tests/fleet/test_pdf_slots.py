"""The placement rows' history-pdf slots and their prefix-term memo.

The vectorized fleet engine resolves each ``(seed, type, bid)`` once to a
slot holding the history pdf and its weighted vector, and memoizes Eq. 8's
two prefix sums per ``(slot, w)``.  Every term must equal the scalar
expressions of :func:`repro.core.provision.expected_execution_time` bit for
bit, and the ``fleet_batch.*`` term counters must count what the memo did.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro import obs
from repro.core.schemes import Scheme
from repro.engine import fleetgrid
from repro.engine.fleetgrid import run_fleet

from test_batch_parity import small_scenario

COUNTERS = ("fleet_batch.eet_term_requests", "fleet_batch.eet_terms", "fleet_batch.pdf_slots")


def _memo(scenario):
    """The placement memo of the scenario's (single) pooled inputs."""
    (inp,) = [v for v in fleetgrid._INPUTS_CACHE.values() if v.types]
    assert inp is fleetgrid.fleet_inputs(scenario)
    return inp.memo


def _scalar_terms(pdf, w_bins, recovery_s, k):
    """``(p_fail, wasted)`` as ``expected_execution_time`` computes them."""
    fail_before = pdf.pdf[:w_bins] if w_bins <= len(pdf.pdf) else pdf.pdf
    p_fail = float(np.sum(fail_before))
    wasted = float(np.sum((k[: len(fail_before)] * pdf.bin_s + recovery_s) * fail_before))
    return p_fail, wasted


def test_slot_terms_equal_the_scalar_expressions_bit_for_bit():
    scenario = small_scenario(scheme=Scheme.ACC, policies=("algorithm1", "diversified"))
    fleetgrid._INPUTS_CACHE.clear()
    run_fleet(scenario, engine="batch")
    memo = _memo(scenario)
    slots = {key: s for key, s in memo.slots.items() if s is not None}
    assert slots and None in memo.slots.values()  # some types never drop to their bid
    for (seed, name, bid, recovery_s), slot in slots.items():
        pdf = memo.pdf(seed, name, bid)
        K = len(pdf.pdf)
        assert memo.slot_pdf[slot] is pdf.pdf
        k = np.arange(K)
        ws = range(1, K + 65)
        got, _ = memo.terms([(slot, min(w, K)) for w in ws])
        want = [_scalar_terms(pdf, w, recovery_s, k) for w in ws]
        assert got == want, (seed, name, bid)


@pytest.mark.parametrize("engine", ["batch", "jax"])
def test_term_counters_count_what_the_memo_did(engine):
    if engine == "jax":
        pytest.importorskip("jax")
    scenario = small_scenario(scheme=Scheme.ACC, seeds=(0, 1, 2))
    fleetgrid._INPUTS_CACHE.clear()
    with obs.Telemetry() as cold:
        first = run_fleet(scenario, engine=engine)
    memo = _memo(scenario)
    requests, evaluated, slots = (cold.counter(c) for c in COUNTERS)
    assert 0 < evaluated <= requests
    assert slots == len(memo.slot_pdf) > 0
    assert evaluated == len(memo.term_vals)

    # the same memo with its rows, score rows and walks dropped: every row
    # is built again, from slots and terms the first run left behind
    memo.rows.clear()
    memo.score_rows.clear()
    memo.walks.clear()
    with obs.Telemetry() as rebuilt:
        again = run_fleet(scenario, engine=engine)
    assert rebuilt.counter("fleet_batch.eet_term_requests") == requests
    assert rebuilt.counter("fleet_batch.eet_terms") == 0
    assert rebuilt.counter("fleet_batch.pdf_slots") == 0
    assert again.results == first.results

    # a warm repeat reads its rows from the memo and still reports the counters
    with obs.Telemetry() as warm:
        run_fleet(scenario, engine=engine)
    assert all(c in warm.counters and warm.counter(c) == 0 for c in COUNTERS)
