"""Sweep program, device to host: the ``sim.fetch`` spans, the scan's finals
and run records copied down."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "sim.fetch"))
