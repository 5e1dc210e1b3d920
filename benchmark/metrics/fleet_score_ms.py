"""EET scoring, host side (``kernels.fleet_step.ops.eet_scores``: padding,
copies up, the program, the scores back on the host): the ``fleet.score``
spans."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "fleet.score"))
