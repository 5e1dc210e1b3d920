"""Engine throughput: reference (scalar) vs batch (SoA NumPy) vs jax.

Usage::

    PYTHONPATH=src python benchmarks/engine_bench.py --quick \
        [--min-speedup 10] [--require-jax-ge-batch] [--profile] \
        [--check-trend] [--overhead-gate 5]

Evaluates the §VII-style grid on every available backend, verifies exact
cross-backend parity on every cell, and writes ``BENCH_engine.json`` (one
``backends`` map: wall time, cells/sec and speedup per backend).  The scheme
set is every bid-limited scheme — **ADAPT included** — so the sweeps the
paper's headline figures need are the ones being gated.  ``--quick`` runs the
acceptance grid — 32 instance types x 11 bids x 5 schemes x 4 seeds — in
seconds; the full grid covers the whole 64-type catalog at the paper's 41-bid
resolution.

CI gates: ``--min-speedup`` fails the run when the batch backend drops below
the given multiple of reference throughput; ``--require-jax-ge-batch`` fails
it when the one-compile jax program does not at least match the batch
backend's speedup; ``--check-trend`` fails it when any backend's speedup
regresses more than ``--trend-tol`` (default 20%) against the last matching
entry of ``BENCH_history.jsonl`` (falling back to the committed
``BENCH_engine.json`` baseline); ``--overhead-gate PCT`` fails it when
running the batch backend under an *active* telemetry collector costs more
than PCT percent over the telemetry-off wall time.

Every run appends one record (commit sha, grid, per-backend speedups, phase
timings) to ``BENCH_history.jsonl`` — the artifact CI uploads so trends
survive across builds.

``--profile`` prints each backend's :class:`~repro.engine.base.PhaseTimings`
(grid build, per-scheme simulation vs billing, scalar fill).

The jax backend is benchmarked when jax is importable (skipped otherwise, or
with ``--skip-jax``).  Every candidate backend gets one untimed warm-up
run (allocator pools, jit compilation) before ``--repeats`` timed runs, of
which the fastest is reported — the gates measure steady-state throughput,
not cold-start noise.  Wall times are
simulation-only (all backends share identical trace materialization, which is
excluded by ``EngineResult.wall_s``).
"""

from __future__ import annotations

import argparse
import json
import logging
import pathlib
import subprocess
import sys

from repro import configure_logging, obs
from repro.core import catalog
from repro.engine import (
    BID_LIMITED_SCHEMES,
    ReferenceEngine,
    Scenario,
    get_engine,
    have_jax,
)
from repro.engine.parity import compare_results

log = logging.getLogger("repro.bench.engine")

HISTORY = "BENCH_history.jsonl"


def quick_scenario() -> Scenario:
    """32 types x 11 bids x 5 schemes x 4 seeds, bids sweeping each type's
    own band (0.50..0.60 x on-demand straddles the calibrated base band).
    Half the catalog: big enough that the lockstep backends amortize their
    fixed per-iteration cost the way the paper's full 64-type study does."""
    types = [it for it in catalog() if it.os == "linux"][:32]
    return Scenario.grid(
        work_s=24 * 3600.0,
        bids=[round(0.50 + 0.01 * i, 3) for i in range(11)],
        instances=types,
        schemes=BID_LIMITED_SCHEMES,
        horizon_days=30.0,
        seeds=(0, 1, 2, 3),
        bid_fractions=True,
    )


def full_scenario() -> Scenario:
    """The full catalog at the paper's 41-bid resolution."""
    return Scenario.grid(
        work_s=24 * 3600.0,
        bids=[round(0.50 + 0.0025 * i, 4) for i in range(41)],
        schemes=BID_LIMITED_SCHEMES,
        horizon_days=30.0,
        seeds=(0, 1, 2, 3),
        bid_fractions=True,
    )


def print_profile(name: str, timings) -> None:
    """Render a backend's :class:`PhaseTimings` phase breakdown."""
    if timings is None:
        log.info("  [%s] no timings recorded", name)
        return
    parts = [f"grid={timings.grid_s * 1e3:.1f}ms"]
    if timings.impl is not None:
        parts.append(f"impl={timings.impl}")
    if timings.sim_s:  # fused device program: one sim phase, all schemes
        parts.append(f"sim(all schemes)={timings.sim_s * 1e3:.1f}ms")
    if timings.scalar_s:
        parts.append(f"scalar_fill={timings.scalar_s * 1e3:.1f}ms")
    log.info("  [%s] %s", name, "  ".join(parts))
    for scheme, t in timings.per_scheme.items():
        log.info(
            "  [%s]   %-6s sim=%7.1fms  bill=%7.1fms",
            name, scheme, t.sim_s * 1e3, t.bill_s * 1e3,
        )


# ---------------------------------------------------------------------------
# Bench history: append-only JSONL, trend gate
# ---------------------------------------------------------------------------


def git_sha(repo_dir=None) -> str | None:
    """Current commit sha, or None outside a usable git checkout."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=repo_dir,
            capture_output=True,
            text=True,
            timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def history_record(record: dict, sha: str | None) -> dict:
    """One BENCH_history.jsonl line: sha + grid + speedups + phase timings."""
    return {
        "sha": sha,
        "grid": record["grid"],
        "backends": {
            name: {
                k: v
                for k, v in entry.items()
                if k in ("wall_s", "cells_per_s", "speedup", "timings")
            }
            for name, entry in record["backends"].items()
        },
        "parity_ok": record["parity_ok"],
    }


def append_history(path, record: dict, sha: str | None) -> dict:
    """Append this run to the history log; returns the appended row."""
    row = history_record(record, sha)
    p = pathlib.Path(path)
    with p.open("a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def load_history(path) -> list[dict]:
    p = pathlib.Path(path)
    if not p.exists():
        return []
    rows = []
    for line in p.read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rows.append(json.loads(line))
        except json.JSONDecodeError:
            log.warning("skipping malformed history line: %.80s", line)
    return rows


def trend_baseline(history: list[dict], grid: dict, fallback: dict | None = None) -> dict | None:
    """The most recent history entry with a matching grid, else the committed
    ``BENCH_engine.json`` record (the previous PR's baseline), else None."""
    for row in reversed(history):
        if row.get("grid") == grid and row.get("parity_ok", True):
            return row
    if fallback is not None and fallback.get("grid") == grid:
        return history_record(fallback, sha=None)
    return None


def check_trend(current: dict, baseline: dict | None, tol: float) -> list[str]:
    """Compare per-backend speedups against the baseline; returns failure
    messages for any backend regressing more than ``tol`` (fractional)."""
    if baseline is None:
        log.info("trend: no matching baseline found, skipping")
        return []
    failures = []
    for name, entry in current["backends"].items():
        sp = entry.get("speedup")
        base = baseline["backends"].get(name, {}).get("speedup")
        if sp is None or base is None:
            continue
        if sp < (1.0 - tol) * base:
            failures.append(
                f"{name} speedup {sp:.1f}x regressed more than {tol:.0%} below "
                f"baseline {base:.1f}x (sha {baseline.get('sha')})"
            )
        else:
            log.info("trend: %s %.1fx vs baseline %.1fx ok", name, sp, base)
    return failures


def measure_overhead(scenario: Scenario, repeats: int) -> tuple[float, float]:
    """(telemetry-off wall, telemetry-on wall) for the batch backend — the
    zero-overhead-when-off contract, measured end to end."""
    engine = get_engine("batch")
    engine.run(scenario)  # warm-up
    off = min(engine.run(scenario).wall_s for _ in range(repeats))
    on = []
    for _ in range(repeats):
        with obs.Telemetry():
            on.append(engine.run(scenario).wall_s)
    return off, min(on)


def main(argv: list[str] | None = None) -> int:
    configure_logging()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="acceptance-sized grid (CI)")
    ap.add_argument(
        "--min-speedup",
        type=float,
        default=None,
        help="fail unless the batch backend >= this multiple of reference throughput",
    )
    ap.add_argument(
        "--require-jax-ge-batch",
        action="store_true",
        help="fail unless the jax backend's speedup >= the batch backend's",
    )
    ap.add_argument(
        "--jax-ge-batch-tol",
        type=float,
        default=0.95,
        help="scheduling-jitter allowance for the relative gate: fail only "
        "when jax < TOL * batch (the reported speedups stay unadjusted)",
    )
    ap.add_argument("--skip-jax", action="store_true", help="do not benchmark the jax backend")
    ap.add_argument(
        "--profile",
        action="store_true",
        help="print per-scheme and per-phase (sim vs billing) timing breakdowns",
    )
    ap.add_argument(
        "--repeats",
        type=int,
        default=2,
        help="runs per backend; the fastest is reported (amortizes allocator "
        "and jit warm-up so the CI gates measure steady-state throughput)",
    )
    ap.add_argument(
        "--out", default="BENCH_engine.json", help="where to write the benchmark record"
    )
    ap.add_argument(
        "--history", default=HISTORY, help="append-only JSONL trend log (CI artifact)"
    )
    ap.add_argument(
        "--check-trend",
        action="store_true",
        help="fail when a backend's speedup regresses more than --trend-tol vs "
        "the last matching BENCH_history.jsonl entry (fallback: the "
        "committed BENCH_engine.json baseline)",
    )
    ap.add_argument(
        "--trend-tol",
        type=float,
        default=0.20,
        help="allowed fractional speedup regression for --check-trend",
    )
    ap.add_argument(
        "--overhead-gate",
        type=float,
        default=None,
        metavar="PCT",
        help="fail when running with an active Telemetry collector is more "
        "than PCT percent slower than with telemetry off (batch backend)",
    )
    args = ap.parse_args(argv)

    scenario = quick_scenario() if args.quick else full_scenario()
    log.info(
        "# engine bench: %d types x %d bids x %d schemes (ADAPT batched) x %d seeds = %d cells",
        len(scenario.instances), len(scenario.bids), len(scenario.schemes),
        len(scenario.seeds), scenario.n_cells,
    )

    ref_engine = ReferenceEngine(keep_runs=False)
    ref = min((ref_engine.run(scenario) for _ in range(args.repeats)), key=lambda r: r.wall_s)
    log.info("reference: %8.3fs  (%9.0f cells/s)", ref.wall_s, ref.cells_per_s)

    backends = ["batch"]
    if not args.skip_jax and have_jax():
        backends.append("jax")

    record = {
        "grid": {
            "n_types": len(scenario.instances),
            "n_bids": len(scenario.bids),
            "n_schemes": len(scenario.schemes),
            "n_seeds": len(scenario.seeds),
            "n_cells": scenario.n_cells,
            "work_h": scenario.work_s / 3600.0,
            "horizon_days": scenario.horizon_days,
            "quick": bool(args.quick),
        },
        "schemes": [s.value for s in scenario.schemes],
        "backends": {
            "reference": {
                "wall_s": ref.wall_s,
                "cells_per_s": ref.cells_per_s,
                "timings": ref.timings.asdict() if ref.timings else None,
            },
        },
        "parity_ok": True,
    }

    speedups: dict[str, float] = {}
    for name in backends:
        engine = get_engine(name)
        # one untimed warm-up per candidate (allocator pools, jit compile):
        # the timed repeats then measure steady-state throughput
        engine.run(scenario)
        res = min((engine.run(scenario) for _ in range(args.repeats)), key=lambda r: r.wall_s)
        report = compare_results(scenario, ref, res)
        if not report.ok:
            log.error("%s", report)
            record["parity_ok"] = False
            pathlib.Path(args.out).write_text(json.dumps(record, indent=2) + "\n")
            return 2
        speedups[name] = ref.wall_s / res.wall_s if res.wall_s > 0 else float("inf")
        record["backends"][name] = {
            "wall_s": res.wall_s,
            "cells_per_s": res.cells_per_s,
            "speedup": speedups[name],
            "timings": res.timings.asdict() if res.timings else None,
        }
        log.info(
            "%-10s %8.3fs  (%9.0f cells/s)  %6.1fx  (parity: exact on %d cells)",
            name + ":", res.wall_s, res.cells_per_s, speedups[name], res.n_cells,
        )
        if args.profile:
            print_profile(name, res.timings)

    out = pathlib.Path(args.out)
    committed = None  # the previous record, before this run overwrites it
    if out.exists():
        try:
            committed = json.loads(out.read_text())
        except json.JSONDecodeError:
            committed = None
    sha = git_sha()
    append_history(args.history, record, sha)
    out.write_text(json.dumps(record, indent=2) + "\n")
    log.info("wrote %s (history: %s)", out, args.history)

    rc = 0
    if args.min_speedup is not None and speedups["batch"] < args.min_speedup:
        log.error(
            "FAIL: batch speedup %.1fx below required %.1fx",
            speedups["batch"], args.min_speedup,
        )
        rc = 1
    if args.require_jax_ge_batch:
        if "jax" not in speedups:
            log.error("FAIL: --require-jax-ge-batch but the jax backend was not benchmarked")
            rc = 1
        elif speedups["jax"] < args.jax_ge_batch_tol * speedups["batch"]:
            log.error(
                "FAIL: jax speedup %.1fx below %.2f x batch (%.1fx)",
                speedups["jax"], args.jax_ge_batch_tol, speedups["batch"],
            )
            rc = 1
    if args.check_trend:
        # drop the just-appended row: a run must not be its own baseline
        history = load_history(args.history)[:-1]
        baseline = trend_baseline(history, record["grid"], fallback=committed)
        for msg in check_trend(record, baseline, args.trend_tol):
            log.error("FAIL (trend): %s", msg)
            rc = 1
    if args.overhead_gate is not None:
        off, on = measure_overhead(scenario, args.repeats)
        pct = 100.0 * (on - off) / off if off > 0 else 0.0
        log.info(
            "telemetry overhead: off=%.3fs on=%.3fs (%+.1f%%, gate %.1f%%)",
            off, on, pct, args.overhead_gate,
        )
        if pct > args.overhead_gate:
            log.error(
                "FAIL: telemetry-on overhead %.1f%% exceeds gate %.1f%%",
                pct, args.overhead_gate,
            )
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
