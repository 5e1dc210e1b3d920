"""The fleet study kind (``benchmark/kinds/fleet.py``), its plain reference
(``benchmark/reference/fleet.py``) and its readers: the reference gives the
program's records and outcomes on acc_stream-shaped and HOUR scenarios, a
sound run is correct, the float32 control and a broken timed path are not,
a study served from the input cache fails, and each reader reads its mean
and nothing from a run without its spans."""

from __future__ import annotations

import copy
import dataclasses
import io
import json

import pytest
from conftest import ROOT, TINY, tiny_study

from benchmark import fleet_roofline, harness
from benchmark.kinds import fleet as kind

#: ``tiny_bench`` cuts every configuration by its entry here
TINY.setdefault("paper_fleet", {"n_types": 8, "ensemble_seeds": [0, 1], "bid_margins": [0.55],
                                "horizon_days": 10.0})

WORKLOAD = "fleet.study"
SEED = 2**31 + 23


def _tiny_study(scheme: str) -> kind.Study:
    """A study of the tiny configuration under ``scheme`` that compares every cell."""
    config = json.loads((ROOT / "benchmark/configs/paper_fleet.json").read_text())
    config.update(TINY["paper_fleet"])
    traffic = json.loads((ROOT / "benchmark/traffic/acc_stream.json").read_text())
    study = kind.Study(config, dict(traffic, scheme=scheme), SEED)
    study.sample = [(p, m, s) for s in config["ensemble_seeds"] for m in config["bid_margins"]
                    for p in config["policies"]]
    return study


@pytest.mark.parametrize("engine", ["jax", "controller"])
@pytest.mark.parametrize("scheme", ["acc", "hour"])
def test_reference_gives_the_programs_records(scheme, engine):
    from repro.engine import fleetgrid, run_fleet

    study = _tiny_study(scheme)
    fleetgrid._INPUTS_CACHE.clear()
    out = run_fleet(study.scenario(), engine=engine)
    kept = study.keep(out)
    assert sum(len(recs) for recs, _ in kept.values()) > 100
    self_terminated = [flags[6] for recs, _ in kept.values() for _, flags, _, _ in recs]
    assert any(self_terminated) == (scheme == "acc")
    assert study.checks([kept]) == {"discrete": 0, "time_gap": 0.0, "cost_gap": 0.0}


def _run(bench, seed=SEED):
    device = harness.device_info(1, require_chip=False)
    return harness.run_cell(bench, WORKLOAD, seed, 0.05, 0, device, 0.0, require_chip=False,
                            log=io.StringIO())


def test_sound_fleet_run_is_correct(tiny_bench):
    result = _run(tiny_bench)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"study_s", "setup_s"}
    assert all(v["value"] == 0 for v in result["checks"].values())


def test_fleet_float32_control_fails(tiny_bench):
    k, study = tiny_study(tiny_bench, WORKLOAD)
    checks = study.control()
    assert [name for name, limit in k.LIMITS.items() if checks[name] > limit]


def test_fleet_study_served_from_the_input_cache_fails(tiny_bench, monkeypatch):
    from repro.engine import fleetgrid

    _, study = tiny_study(tiny_bench, WORKLOAD)
    compiles = harness.CompileCounter()
    harness.one_study(study, -1, compiles, annotate=False)
    monkeypatch.setattr(fleetgrid, "_INPUTS_CACHE", _KeepingCache(fleetgrid._INPUTS_CACHE))
    harness.one_study(study, 0, compiles, annotate=False)
    _, _, why = harness.one_study(study, 1, compiles, annotate=False)
    assert why and "fleet.inputs" in why


class _KeepingCache(dict):
    """An input pool that ignores being emptied."""

    def clear(self):
        pass


def _break_costs(results):
    for res in results.values():
        res.records[:] = [dataclasses.replace(r, cost=r.cost * (1 + 1e-6)) for r in res.records]


def _drop_last_records(results):
    for res in results.values():
        del res.records[-3:]


def _unfinish(results):
    for res in results.values():
        for o in res.outcomes.values():
            o.completed, o.completion_time = False, float("inf")


@pytest.mark.parametrize("fault", [_break_costs, _drop_last_records, _unfinish],
                         ids=["answer_altered", "records_left_out", "jobs_unfinished"])
def test_broken_fleet_path_is_not_correct(tiny_bench, monkeypatch, fault):
    from repro.fleet import batch

    inner = batch.run_fleet_batch

    def broken(*a, **k):
        results = inner(*a, **k)
        fault(results)
        return results

    monkeypatch.setattr(batch, "run_fleet_batch", broken)
    result = _run(tiny_bench)
    assert result["correct"] is False, result["checks"]


# -- the readers ---------------------------------------------------------------

#: each span reader, the span it reads and its per-study seconds at k = 1
SPAN_READERS = {"fleet_inputs_ms": ("fleet.inputs", 0.30),
                "fleet_place_ms": ("fleet.place_wave", 0.40),
                "fleet_sim_ms": ("fleet.sim_wave", 0.20),
                "fleet_replay_ms": ("fleet.replay", 0.05),
                "fleet_score_ms": ("fleet.score", 0.01)}
NEW = list(SPAN_READERS) + ["eet_device_ms", "eet_roofline"]


def _study(i: int, spans: bool) -> harness.StudyRecord:
    """A hand-made fleet study: with ``spans``, the program's fleet spans
    (two waves of each kind, the score inside a placement wave) and the EET
    program's scored cells."""
    from repro.obs import Span, Telemetry

    k = 1.0 + i
    children = []
    if spans:
        d = {n: k * s for n, s in SPAN_READERS.values()}
        children = [Span("fleet.inputs", 0.0, d["fleet.inputs"])]
        for _ in range(2):
            score = Span("fleet.score", 0.0, d["fleet.score"] / 2, {"impl": "jax"})
            children += [
                Span("fleet.place_wave", 0.0, d["fleet.place_wave"] / 2, children=[score]),
                Span("fleet.sim_wave", 0.0, d["fleet.sim_wave"] / 2, {"scheme": "acc"})]
        children.append(Span("fleet.replay", 0.0, d["fleet.replay"],
                             children=[Span("fleet.cell", 0.0, d["fleet.replay"] / 2)]))
    root = Span("bench.study", 0.0, k * 1.2, children=children)
    tel = Telemetry()
    tel.spans = [root]
    if spans:
        tel.count("fleet_step.cells", 155_680)
    return harness.StudyRecord(i, root.dur, tel, root, f"bench.study {i}", 0)


def _run_data(spans: bool, trace=None) -> harness.RunData:
    peaks = {"hbm_bytes_per_s": 819e9}
    return harness.RunData([_study(i, spans) for i in range(3)], trace, {}, peaks)


@pytest.mark.parametrize("name", list(SPAN_READERS))
def test_span_reader_reads_its_mean(name):
    _, per_study = SPAN_READERS[name]
    # the studies' k: 1, 2 and 3, mean 2
    assert harness.load_reader(name).read(_run_data(True)) == pytest.approx(1e3 * per_study * 2)


def test_eet_readers_read_the_program_time():
    trace = {"program_s": {fleet_roofline.EET_PROGRAM: 3 * 0.5e-3, "jit_fn": 0.4}}
    run = _run_data(True, trace)
    assert harness.load_reader("eet_device_ms").read(run) == pytest.approx(0.5)
    least = fleet_roofline.eet_bytes(3 * 155_680)
    assert harness.load_reader("eet_roofline").read(run) == pytest.approx(
        100.0 * least / 819e9 / 1.5e-3)


@pytest.mark.parametrize("name", NEW)
def test_fleet_reader_is_silent_without_its_spans(name):
    trace = {"program_s": {"jit_fn": 0.4}}
    assert harness.load_reader(name).read(_run_data(False, trace)) is None
    empty = harness.RunData(studies=[], trace=None, shapes={}, peaks={})
    assert harness.load_reader(name).read(empty) is None


def test_eet_bytes():
    # p_fail, wasted, w_scaled (8 B each) and avail (1 B) read, the score written
    assert fleet_roofline.eet_bytes(1) == 33
    assert fleet_roofline.eet_bytes(16 * 28) == 16 * 28 * 33


def test_span_readers_on_a_tiny_fleet_run(tiny_bench):
    _, study = tiny_study(tiny_bench, WORKLOAD)
    compiles = harness.CompileCounter()
    harness.one_study(study, -1, compiles, annotate=False)
    records = [harness.one_study(study, i, compiles, annotate=False)[0] for i in range(2)]
    run = harness.RunData(records, None, study.shapes(), {})
    got = {name: harness.load_reader(name).read(run) for name in SPAN_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["fleet_score_ms"] <= got["fleet_place_ms"]
    wall_ms = 1e3 * sum(r.wall_s for r in records) / len(records)
    assert sum(got[n] for n in ("fleet_inputs_ms", "fleet_place_ms", "fleet_sim_ms",
                                "fleet_replay_ms")) <= wall_ms


def test_tiny_fleet_config_keeps_the_committed_keys():
    config = json.loads((ROOT / "benchmark/configs/paper_fleet.json").read_text())
    tiny = copy.deepcopy(config)
    tiny.update(TINY["paper_fleet"])
    assert set(tiny) == set(config)
