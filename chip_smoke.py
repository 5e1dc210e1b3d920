"""Chip smoke: the scenario main path on one TPU, checked against the NumPy engine.

Run from the root of a checkout, on a machine whose default JAX device is a
TPU::

    python chip_smoke.py

One process, one chip, three phases:

* ``precision`` — how float64, which XLA emulates on a TPU, fares on the
  device, over 2**20 values per probe: the values an arbitrary float64 loses
  on the way to the device, tiny values flushed to zero, whole-number add,
  subtract, multiply and divide against NumPy, ADAPT's age bin
  ``int(age / 60)`` of whole-second ages, and ADAPT's hazard test on the
  exact grid's tables (device against NumPy, and NumPy on the tables as the
  device holds them).  It requires whole-number add, subtract and multiply
  to be exact and every age bin within 30 days to be right; the rest is
  printed.
* ``sweep`` — the full-catalog study grid (``benchmarks/engine_bench.py``
  ``full_scenario()``: 64 types x 41 bids x 4 seeds x the five bid-limited
  schemes over 30 days).  ``run(scenario)`` must resolve to the batch
  engine, which is exact on every platform; the device program
  (``engine="jax"``) runs the same grid on the chip.  It is not
  bit-identical to the NumPy engine there: the grid's period times are
  arbitrary reals, which lose bits on the device, and near-tie decisions
  flip.  The phase prints, per field and scheme, how many cells differ and
  by how much, and requires

  - a warm re-run that traces nothing (``repro.obs.retrace_guard``) and
    returns the cold run's bits;
  - no NaN, and ``completion_time`` finite exactly where ``completed``;
  - per scheme and discrete field, no more differing cells than
    :data:`DISCRETE_CAPS` allows: none for NONE, OPT and EDGE, the counts
    the chip gave run after run for HOUR and ADAPT;
  - in every cell whose discrete outcomes agree with the batch engine, float
    fields within :data:`FLOAT_BOUND` of it (times relative to the horizon,
    cost relative to itself), and for ADAPT within :data:`ADAPT_FLOAT_CAPS`
    (one flipped decision tick can move an ADAPT checkpoint and keep every
    count);
  - ``==`` with the batch engine on every field of :func:`exact_scenario`,
    a grid whose times are exact in emulated float64, for NONE, OPT, HOUR
    and EDGE, and for ADAPT in all but the cells the chip has always given
    (:data:`EXACT_GRID_CAPS`).
* ``fleet`` — the fleet bench grid (``benchmarks/fleet_study.py``
  ``bench_scenario(quick=False)``) through ``run_fleet(..., engine="jax")``,
  whose EET scoring is jitted onto the chip.  Its grid must equal
  ``engine="batch"`` bit for bit, cold and on a warm run that scores every
  row on the device again (the fleet memo's score rows and walks cleared)
  and traces nothing.

The timings printed are smoke timings of one cold and one warm run, not
benchmark numbers.  On success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``; on
any failure the script exits non-zero without it.  There is no CPU fallback:
a default device that is not a TPU is a failure.
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

from engine_bench import full_scenario  # noqa: E402
from fleet_study import _grids_equal, bench_scenario  # noqa: E402
from repro.core import Scheme, step_trace  # noqa: E402
from repro.engine import BID_LIMITED_SCHEMES, Scenario, get_engine, run, run_fleet  # noqa: E402
from repro.engine.fleetgrid import fleet_inputs  # noqa: E402
from repro.engine.parity import COMPARED, compare_results  # noqa: E402
from repro.kernels.fleet_step import ops as fleet_ops  # noqa: E402
from repro.obs import retrace, retrace_guard  # noqa: E402

#: fields whose values are counts or flags
DISCRETE = ("completed", "n_checkpoints", "n_kills", "n_self_terminations")

#: bound on the float fields of cells whose discrete outcomes agree
FLOAT_BOUND = 1e-12

#: Most full-catalog cells in which a discrete field of the device program
#: may differ from the batch engine, per scheme; 0 where not listed.  The
#: counts are those the chip gave, repeated exactly run to run (PERF.md):
#: real period times and ADAPT's survival tables lose bits on the way to
#: the device, and decisions at near-ties flip.
DISCRETE_CAPS = {
    Scheme.HOUR: {"n_checkpoints": 1},
    Scheme.ADAPT: {"completed": 5, "n_checkpoints": 1073, "n_kills": 155},
}

#: Ceiling on ADAPT's float differences in full-catalog cells whose discrete
#: fields agree (times relative to the horizon, cost relative to itself):
#: the largest the chip gave, rounded up in the second digit.
ADAPT_FLOAT_CAPS = {"completion_time": 4.6e-3, "work_lost_s": 4.6e-3, "cost": 1.3e-1}

#: Most cells of :func:`exact_scenario` that may differ from the batch
#: engine, per scheme; 0 where not listed.  ADAPT's survival tables are
#: not exact in emulated float64 (the ``precision`` phase's hazard probe).
EXACT_GRID_CAPS = {Scheme.ADAPT: 6}

#: the JAX monitoring event that times one backend (XLA) compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def exact_scenario() -> Scenario:
    """Eight explicit markets whose times are exact in emulated float64:
    period boundaries, work and the simulation constants are whole seconds
    far below 2**49, so adds, subtracts, multiplies and compares of them are
    exact on the chip (prices only reach the host-side biller).  The five
    bid-limited schemes over 5 bids, about 25 days each."""
    rng = np.random.default_rng(11)
    traces = []
    for _ in range(8):
        durations = rng.integers(600, 6 * 3600, size=200)
        starts = np.concatenate([[0], np.cumsum(durations)[:-1]]).astype(float)
        prices = rng.integers(300, 420, size=200) / 1000.0
        traces.append(step_trace(list(zip(starts, prices)), float(durations.sum())))
    return Scenario(
        work_s=20 * 3600.0,
        bids=(0.33, 0.35, 0.37, 0.39, 0.41),
        schemes=BID_LIMITED_SCHEMES,
        traces=tuple(traces),
        labels=tuple(f"exact{m}" for m in range(len(traces))),
    )


@contextlib.contextmanager
def compile_seconds():
    """Sum the backend compile time JAX reports inside the block."""
    import jax

    total = [0.0]

    def listen(event, duration, **_):
        if event == COMPILE_EVENT:
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def rel_diff(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise relative difference; equal values (inf == inf) give 0."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        d = np.abs(a - b) / np.maximum(np.maximum(np.abs(a), np.abs(b)), 1e-300)
    return np.where(a == b, 0.0, np.nan_to_num(d, nan=np.inf))


def count_line(name: str, got: np.ndarray, want: np.ndarray) -> int:
    """Print and return how many of ``got`` differ from ``want``, with the
    largest relative difference."""
    differ = int((got != want).sum())
    print(f"[precision] {name}: {differ} of {want.size} differ, largest relative "
          f"{rel_diff(got, want).max():.3e}", flush=True)
    return differ


def precision_phase() -> bool:
    import jax
    import jax.numpy as jnp

    from repro.engine import kernels
    from repro.engine.batch import grid_and_tables
    from repro.engine.jax_backend import _require_jax

    _require_jax()  # float64 on, as the engine runs it
    n = 1 << 20
    rng = np.random.default_rng(0)
    x = rng.random(n) * 2.6e6  # arbitrary reals up to a 30-day horizon
    count_line("arbitrary float64 after a round trip to the device",
               np.asarray(jax.device_put(x)), x)
    tiny = rng.random(n) * 1e-300
    flushed = int((np.asarray(jax.device_put(tiny)) == 0).sum())
    print(f"[precision] values below 1e-300 flushed to zero: {flushed} of {n}", flush=True)

    a = rng.integers(1, 1 << 22, size=n).astype(np.float64)
    b = rng.integers(1, 1 << 22, size=n).astype(np.float64)
    arith = jax.jit(lambda a, b: (a + b, a - b, a * b, a / b))
    exact = True
    for name, got, want in zip(("add", "subtract", "multiply", "divide"),
                               arith(a, b), (a + b, a - b, a * b, a / b)):
        differ = count_line(f"whole-number {name}", np.asarray(got), want)
        if name != "divide":
            exact &= differ == 0

    # ADAPT's age bin int(age / 60), as the scan computes it (traced divisor)
    trunc = jax.jit(lambda x, s: (x / s).astype(jnp.int64))
    k = np.arange(n, dtype=np.int64)
    ages = k * 60.0 + rng.integers(0, 60, size=n)  # whole seconds anywhere in a bin
    got = np.asarray(trunc(ages, 60.0))
    in_horizon = ages <= 30 * 86400.0
    bins_right = count_line("int(age / 60), whole-second ages up to 30 days",
                            got[in_horizon], k[in_horizon]) == 0
    wrong = np.flatnonzero(got != k)
    print(f"[precision] int(age / 60) beyond: smallest wrong bin "
          f"{int(k[wrong[0]]) if wrong.size else None}", flush=True)

    # ADAPT's hazard test on the tables of the exact grid, at whole-second
    # ages and unsaved work: how often the device decides otherwise, and how
    # much of that the table's rounding on the way to the device explains
    small = exact_scenario()
    _, tab = grid_and_tables(small, small.materialize(), True)
    p = small.params
    cell = rng.integers(0, len(tab.off), size=n)
    age = 300.0 * rng.integers(2, 2000, size=n)  # t_r + ticks of 600 s and t_c of 300 s
    unsaved = rng.integers(0, 20 * 3600, size=n).astype(np.float64)

    def decide(xp, age, unsaved, flat, off, top):
        return kernels.adapt_decision(xp, age, unsaved, flat, off, top, tab.bin_s,
                                      tab.n_bins, p.t_c, p.t_r, p.adapt_interval_s)

    off, top = tab.off[cell], tab.top[cell]
    host = decide(np, age, unsaved, tab.flat, off, top)
    flat_dev = np.asarray(jax.device_put(tab.flat))
    count_line("exact grid's ADAPT survival tables after a round trip", flat_dev, tab.flat)
    host_rounded = decide(np, age, unsaved, flat_dev, off, top)
    dev = np.asarray(jax.jit(lambda *arr: decide(jnp, *arr))(age, unsaved, tab.flat, off, top))
    print(f"[precision] ADAPT hazard test on the exact grid's tables: the device decides "
          f"otherwise in {int((dev != host).sum())} of {n}; NumPy on the tables as the "
          f"device holds them, in {int((host_rounded != host).sum())}; the device vs "
          f"that, in {int((dev != host_rounded).sum())}", flush=True)
    print(f"[precision] whole-number add/subtract/multiply exact: {exact}; ADAPT age "
          f"bins right within 30 days: {bins_right}", flush=True)
    return exact and bins_right


def sweep_phase() -> bool:
    scenario = full_scenario()
    print(f"[sweep] {scenario.n_cells} cells x {len(scenario.schemes)} schemes", flush=True)
    ref, batch_s = timed(lambda: run(scenario))
    print(f"[sweep] engine='auto' resolves to {ref.engine!r}", flush=True)
    ok = ref.engine == "batch"
    engine = get_engine("jax")
    with compile_seconds() as comp:
        cold, cold_s = timed(lambda: run(scenario, engine=engine))
    with retrace_guard("spot_sweep") as guard:
        warm, warm_s = timed(lambda: run(scenario, engine=engine))
    print(
        f"[sweep] smoke timings, not benchmark numbers: auto (batch) {batch_s:.3f}s, "
        f"jax cold {cold_s:.3f}s (backend compile {comp[0]:.3f}s), jax warm "
        f"{warm_s:.3f}s, retraces on the warm run {guard.new_traces}",
        flush=True,
    )
    for field in COMPARED:
        same = np.array_equal(getattr(cold, field), getattr(warm, field))
        ok &= same
        if not same:
            print(f"[sweep] FAIL: the warm run's {field} differs from the cold run's")
    floats = [getattr(cold, f) for f in COMPARED if f not in DISCRETE]
    consistent = not any(np.isnan(x).any() for x in floats) and np.array_equal(
        np.isfinite(cold.completion_time), cold.completed
    )
    print(f"[sweep] jax result: no NaN, completion_time finite exactly where completed: {consistent}")
    ok &= consistent

    agree = np.ones(ref.shape, dtype=bool)
    for field in DISCRETE:
        agree &= getattr(ref, field) == getattr(cold, field)
    print(f"[sweep] jax vs batch on the chip, {int(agree.sum())} of {agree.size} cells "
          "agree on every discrete field:", flush=True)
    horizon_s = scenario.horizon_days * 86400.0
    for field in COMPARED:
        r, c = getattr(ref, field), getattr(cold, field)
        differ = ~(r == c)
        print(f"[sweep]   {field}: {int(differ.sum())} cells differ, largest relative "
              f"difference {rel_diff(r, c).max():.3e}", flush=True)
        if field not in DISCRETE:
            scale = np.abs(r) if field == "cost" else horizon_s
            with np.errstate(invalid="ignore", divide="ignore"):
                errs = np.where(agree & differ, np.abs(r - c) / scale, 0.0)
        for s, scheme in enumerate(ref.schemes):
            n_s = int(differ[:, :, s].sum())
            if field in DISCRETE:
                cap = DISCRETE_CAPS.get(scheme, {}).get(field, 0)
                within = n_s <= cap
                line = f"{n_s} cells (at most {cap})"
            else:
                cap = ADAPT_FLOAT_CAPS[field] if scheme is Scheme.ADAPT else FLOAT_BOUND
                err = float(errs[:, :, s].max())
                within = err <= cap
                line = (f"{n_s} cells, largest relative {rel_diff(r[:, :, s], c[:, :, s]).max():.3e}; "
                        f"where the discrete fields agree {err:.3e} of "
                        f"{'the cost' if field == 'cost' else 'the horizon'} (at most {cap:g})")
            ok &= within
            if n_s or not within:
                print(f"[sweep]     {scheme.value}: {line}: {'ok' if within else 'FAIL'}", flush=True)
    print(f"[sweep] the device program is exact on the full catalog: "
          f"{bool(agree.all()) and all(np.array_equal(getattr(ref, f), getattr(cold, f)) for f in COMPARED)}"
          f"; engine='auto' stays on batch", flush=True)

    small = exact_scenario()
    report = compare_results(small, run(small, engine="batch"), run(small, engine=engine))
    cells = {scheme.value: set() for scheme in small.schemes}
    for mm in report.mismatches:
        cells[mm.scheme].add((mm.market, mm.bid))
    caps = {scheme.value: EXACT_GRID_CAPS.get(scheme, 0) for scheme in small.schemes}
    exact = all(len(cells[k]) <= caps[k] for k in cells)
    print(f"[sweep] exact grid ({small.n_cells} cells), jax cells differing from batch "
          f"per scheme: { {k: len(v) for k, v in cells.items()} }, at most {caps}: "
          f"{'ok' if exact else 'FAIL'}", flush=True)
    for mm in report.mismatches:
        print(f"[sweep]   {mm.field}[{mm.market} bid={mm.bid:.3f} {mm.scheme}] "
              f"batch={mm.reference!r} jax={mm.candidate!r}", flush=True)
    return ok and exact


def fleet_diffs(ref, got) -> dict[str, int]:
    """Per-field count of differing job outcomes, plus differing record logs."""
    counts = {"records": 0, "completed": 0, "completion_time": 0, "cost": 0,
              "n_kills": 0, "n_migrations": 0}
    for key, a in ref.results.items():
        b = got.results[key]
        counts["records"] += b.records != a.records
        for job_id, oa in a.outcomes.items():
            ob = b.outcomes[job_id]
            for f in list(counts)[1:]:
                counts[f] += getattr(oa, f) != getattr(ob, f)
    return counts


def fleet_phase() -> bool:
    scenario = bench_scenario(quick=False)
    ref, batch_s = timed(lambda: run_fleet(scenario, engine="batch"))
    before = retrace.trace_count(fleet_ops.TRACE_SCOPE)
    with compile_seconds() as comp:
        cold, cold_s = timed(lambda: run_fleet(scenario, engine="jax"))
    traced = retrace.trace_count(fleet_ops.TRACE_SCOPE) - before
    # the warm run must score on the device again, not read the cold run's
    # rows: forget the memo's score rows and the walks that read them
    memo = fleet_inputs(scenario).memo
    memo.score_rows.clear()
    memo.walks.clear()
    with retrace_guard(fleet_ops.TRACE_SCOPE) as guard:
        warm, warm_s = timed(lambda: run_fleet(scenario, engine="jax"))
    rescored = len(memo.score_rows.get("jax", {}))
    print(
        f"[fleet] {len(ref.cells)} cells of {scenario.n_jobs} jobs; smoke timings, "
        f"not benchmark numbers: batch {batch_s:.3f}s, jax cold {cold_s:.3f}s "
        f"(backend compile {comp[0]:.3f}s, {traced} scoring programs traced), "
        f"jax warm {warm_s:.3f}s ({rescored} score rows scored again on the device, "
        f"retraces {guard.new_traces})",
        flush=True,
    )
    ok = traced > 0 and rescored > 0
    for name, got in (("cold", cold), ("warm", warm)):
        equal = _grids_equal(ref, got)
        print(f"[fleet] {name} grid equals batch bit for bit: {equal}", flush=True)
        if not equal:
            print(f"[fleet]   differing (cells' records, job outcomes): {fleet_diffs(ref, got)}")
        ok &= equal
    return ok


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("FAIL: the default JAX device is not a TPU (no CPU fallback)", file=sys.stderr)
        return 2
    results = {"precision": precision_phase(), "sweep": sweep_phase(), "fleet": fleet_phase()}
    for phase, ok in results.items():
        print(f"phase {phase}: {'ok' if ok else 'FAILED'}", flush=True)
    if not all(results.values()):
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
