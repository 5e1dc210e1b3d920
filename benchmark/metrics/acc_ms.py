"""ACC walker (``engine.batch._run_acc``, host): the self time of the
``sim`` span with ``scheme=acc``."""

from benchmark.metrics import mean_ms, spans


def read(run):
    def per_study(r):
        found = spans(r, "sim", scheme="acc")
        return sum(s.self_dur for s in found) if found else None

    return mean_ms(run, per_study)
