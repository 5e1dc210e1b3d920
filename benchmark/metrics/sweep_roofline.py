"""Sweep program: the least bytes a study's scan must move
(``benchmark.roofline.sweep_bytes``) at the peak bandwidth, as a share of
its device time."""

from benchmark.metrics import SWEEP_PROGRAM, roofline_pct


def read(run):
    per_study = run.shapes.get("sweep_bytes_per_study")
    if per_study is None:
        return None
    return roofline_pct(run, per_study * len(run.studies), SWEEP_PROGRAM)
