"""Fleet simulation waves (``fleet.batch._BatchFleet._sim_wave``, host: the
attempts of a round and their bills, ACC leases included): the
``fleet.sim_wave`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "fleet.sim_wave"))
