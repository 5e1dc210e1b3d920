"""Fleet placement waves (``fleet.batch._BatchFleet._place_wave``, host,
its EET scoring included): the ``fleet.place_wave`` spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "fleet.place_wave"))
