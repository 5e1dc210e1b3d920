"""EET scoring program: the least bytes of the entries it scored
(``fleet_step.cells``, ``benchmark.fleet_roofline.eet_bytes``) at the peak
bandwidth, as a share of its device time."""

from benchmark.fleet_roofline import EET_PROGRAM, eet_bytes
from benchmark.metrics import roofline_pct


def read(run):
    cells = sum(r.tel.counter("fleet_step.cells") for r in run.studies)
    if not cells:
        return None
    return roofline_pct(run, eet_bytes(int(cells)), EET_PROGRAM)
