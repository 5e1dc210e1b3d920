"""Sweep program (``kernels.spot_sweep``, the scan): its device time in the
trace, per study."""

from benchmark.metrics import SWEEP_PROGRAM, program_s


def read(run):
    secs = program_s(run, SWEEP_PROGRAM)
    return None if secs is None else 1e3 * secs / len(run.studies)
