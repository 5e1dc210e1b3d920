"""Market materialization (``Scenario.materialize``): the ``materialize``
spans, read inside the program (``materialize_ms`` is the same phase
measured from outside)."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "materialize"))
