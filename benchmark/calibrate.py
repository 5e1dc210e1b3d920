"""Readings that set the limits of ``correct``: the program's and the control's.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --controls 3 --first-seed <n>

In one process on the chip, runs one study of the cell per seed through the
timed path (after one warm-up study) and compares its result with the
reference as a benchmark run does; then the control, the reference with
every trace time, price and result held in float32, in the program's place
on the first ``--controls`` seeds.  Prints one JSON line per reading and, last,
the lower reading of each compared number (the largest the program gives)
and the upper (the smallest the control gives), beside the limit in force.
Limits are set between the two by hand, as PERF.md records.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as benchmark/run.py: no fixed /tmp path
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmark import harness  # noqa: E402


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=3_000_000_000)
    args = p.parse_args(argv)
    bench = harness.load_benchmark()
    cell, config, traffic, _, _ = harness.cell_inputs(bench, args.workload)
    try:
        harness.device_info(int(cell["chips"]))
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    harness.configure_jax()
    kind = importlib.import_module(f"benchmark.kinds.{config['kind']}")
    compiles = harness.CompileCounter()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    program, control = [], []
    for i, seed in enumerate(seeds):
        study = kind.Study(config, traffic, seed)
        if i == 0:
            harness.one_study(study, -1, compiles, annotate=False)
        record, out, why = harness.one_study(study, i, compiles, annotate=False)
        t0 = time.perf_counter()
        checks = study.checks([study.keep(out)])
        program.append(checks)
        print(json.dumps({"seed": seed, "side": "program", "why": why, "study_s": record.wall_s,
                          "reference_s": time.perf_counter() - t0, **checks}), flush=True)
        if i < args.controls:
            t0 = time.perf_counter()
            readings = study.control()
            control.append(readings)
            print(json.dumps({"seed": seed, "side": "control",
                              "control_s": time.perf_counter() - t0, **readings}), flush=True)
    summary = {k: {"lower": max(r[k] for r in program),
                   "upper": min(r[k] for r in control) if control else None,
                   "limit": kind.LIMITS[k]} for k in kind.LIMITS}
    print(json.dumps({"workload": args.workload, "seeds": len(seeds), "readings": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
