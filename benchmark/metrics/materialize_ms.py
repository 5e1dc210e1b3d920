"""Market materialization (``Scenario.materialize``): the study's wall time
less its ``engine.run`` span, which starts once the traces exist."""

from benchmark.metrics import mean_ms, spans


def read(run):
    def per_study(r):
        roots = spans(r, "engine.run")
        return r.wall_s - sum(s.dur for s in roots) if roots else None

    return mean_ms(run, per_study)
