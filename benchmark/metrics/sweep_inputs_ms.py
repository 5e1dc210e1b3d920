"""Sweep program, host side (``kernels.spot_sweep.ops.scan_arrays``): the
``sim.inputs`` spans, the scan's host arguments built."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    return mean_ms(run, total_s(run, "sim.inputs"))
