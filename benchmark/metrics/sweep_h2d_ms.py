"""Sweep program, host to device: the ``sim.h2d`` spans, the scan's
arguments copied up until they are on the device."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "sim.h2d"))
