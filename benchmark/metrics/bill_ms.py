"""Host billing (``engine.batch._bill_runs_flat``): the ``bill`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "bill"))
