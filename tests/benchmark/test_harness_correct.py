"""What decides ``correct``: sound runs pass, the float32 control fails, and
a run whose timed path is broken underneath comes out not correct."""

from __future__ import annotations

import io

import numpy as np
import pytest
from conftest import tiny_study

from benchmark import harness

WORKLOADS = ["catalog.study", "catalog.acc"]


def _run(bench, workload, seed=2**31 + 11):
    device = harness.device_info(1, require_chip=False)
    return harness.run_cell(bench, workload, seed, 0.05, 0, device, 0.0, require_chip=False,
                            log=io.StringIO())


def _over(kind, checks):
    return [k for k, limit in kind.LIMITS.items() if checks[k] > limit]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(tiny_bench, workload):
    result = _run(tiny_bench, workload)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"study_s", "setup_s"}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_float32_control_fails(tiny_bench, workload):
    kind, study = tiny_study(tiny_bench, workload)
    assert _over(kind, study.control())


def _sweep_fault(fault):
    """Break the device sweep's outputs where they are produced."""
    from repro.kernels.spot_sweep import ops

    inner = ops.spot_sweep_grid

    def broken(schemes, grid, scenario, *a, **k):
        outs, info = inner(schemes, grid, scenario, *a, **k)
        for out in outs.values():
            n = len(out["cost"])
            if fault == "answer_altered":
                out["cost"] = out["cost"] * (1 + 1e-4)
            elif fault == "half_left_out":
                out["completed"][n // 2:] = False
                out["completion_time"][n // 2:] = np.inf
                out["cost"][n // 2:] = 0.0
            elif fault == "state_unchanged":
                out["completed"][:] = False
                out["completion_time"][:] = np.inf
                for f in ("n_checkpoints", "n_kills", "work_lost_s", "cost"):
                    out[f][:] = 0
        return outs, info

    return ops, "spot_sweep_grid", broken


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out", "state_unchanged"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_timed_path_is_not_correct(tiny_bench, monkeypatch, workload, fault):
    module, name, broken = _sweep_fault(fault)
    monkeypatch.setattr(module, name, broken)
    result = _run(tiny_bench, workload)
    assert result["correct"] is False, result["checks"]
