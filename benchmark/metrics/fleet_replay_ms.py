"""Fleet replay (``fleet.batch._BatchFleet._replay_all``, host: each cell's
records, counters and outcomes in the controller's event order): the
``fleet.replay`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "fleet.replay"))
