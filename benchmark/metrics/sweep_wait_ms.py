"""Sweep program, the host blocked on the chip: the ``sim.device`` spans,
from the scan's dispatch until its outputs are ready."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "sim.device"))
