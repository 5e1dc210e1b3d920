"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout whose ``src/`` holds the program; the cell
is looked up in ``BENCHMARK.json``.  Exits 3, printing no result, when JAX
finds no accelerator or fewer chips than the cell asks for.
"""

import time

T0 = time.time()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime would keep its logs under a fixed /tmp path; a run writes
# only inside its checkout and its own home and temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
