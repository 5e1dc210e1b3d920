"""Period grid (``engine.batch.grid_and_tables``): the ``grid`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "grid"))
