"""Fleet inputs (``engine.fleetgrid.fleet_inputs`` on a cache miss: the
evaluation traces, the histories, the job streams and the placement memo):
the ``fleet.inputs`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "fleet.inputs"))
