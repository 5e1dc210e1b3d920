"""EET scoring program (``kernels.fleet_step``, ``jit_eet_scores_jax``): its
device time in the trace, per study."""

from benchmark.fleet_roofline import EET_PROGRAM
from benchmark.metrics import program_s


def read(run):
    secs = program_s(run, EET_PROGRAM)
    return None if secs is None else 1e3 * secs / len(run.studies)
