"""Sweep program, device to host: the bytes the program counts as fetched
(``sweep.d2h_bytes``) over the seconds of the ``sim.fetch`` spans, in GB/s,
both as means per study."""

from benchmark.metrics import mean_ms, total_s


def read(run):
    fetch_ms = mean_ms(run, total_s(run, "sim.fetch"))
    moved = [r.tel.counter("sweep.d2h_bytes") for r in run.studies]
    if fetch_ms is None or not all(moved):
        return None
    return sum(moved) / len(moved) / (fetch_ms * 1e-3) / 1e9
