"""JaxEngine ↔ reference parity: the jit/lax.scan backend on the same grids.

Skipped (not failed) when jax is absent; with jax present the JAX backend
must pass the *same* exact-equality parity suite as BatchEngine — float64
elementwise ops are IEEE-exact on CPU and the kernels are shared
(:mod:`repro.engine.kernels`), so agreement is bitwise, ADAPT's binned-hazard
decisions included.
"""

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import Scheme, SimParams, catalog, get_instance, step_trace, synthetic_trace
from repro.engine import (
    BID_LIMITED_SCHEMES,
    JaxEngine,
    Scenario,
    assert_parity,
    get_engine,
    have_jax,
    run,
)

IT = get_instance("m1.xlarge")


def test_registry_resolves_jax_backend():
    from repro.engine import BatchEngine

    assert have_jax()
    eng = get_engine("jax")
    assert isinstance(eng, JaxEngine) and eng.name == "jax"
    # auto is the exact NumPy batch backend (on a TPU too: see get_engine)
    assert isinstance(get_engine("auto"), BatchEngine)
    assert run(Scenario.from_trace(synthetic_trace(IT, 3, seed=0), 3600.0, [0.36])).engine == "batch"


def test_unknown_engine_name_lists_the_known_engines():
    with pytest.raises(ValueError, match=r"'pallas'; expected auto\|batch\|reference\|jax$"):
        get_engine("pallas")


@pytest.mark.parametrize(
    "env_dir, backend", [(None, "tpu"), (None, "cpu"), ("custom", "tpu"), ("custom", "cpu")]
)
def test_compile_cache_dir(tmp_path, env_dir, backend):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise accelerator
    programs cache at ``.jax_cache`` in the source checkout, and XLA:CPU
    (whose entries are tied to the host's instruction set) keeps none.  The
    backend query is steered in a fresh interpreter; nothing runs on a TPU."""
    import os
    import pathlib
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = (
        "import jax\n"
        f"jax.default_backend = lambda: {backend!r}\n"
        "from repro.engine.jax_backend import _require_jax\n"
        "_require_jax()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr
    if env_dir is not None:
        want = str(tmp_path / env_dir)
    else:
        checkout = pathlib.Path(__file__).resolve().parents[2]
        want = str(checkout / ".jax_cache") if backend == "tpu" else "None"
    assert out.stdout.strip().splitlines()[-1] == want


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("work_h", [5.0, 40.0, 200.0])
def test_jax_parity_synthetic_trace(seed, work_h):
    tr = synthetic_trace(IT, 30, seed=seed)
    sc = Scenario.from_trace(
        tr,
        work_h * 3600.0,
        bids=[0.36 + 0.001 * i for i in range(6)],
        schemes=BID_LIMITED_SCHEMES,
    )
    assert_parity(sc, engine="jax")


def test_jax_parity_extreme_bids_and_resume():
    """Never-available, always-available, and mid-job resume cells."""
    tr = synthetic_trace(IT, 30, seed=7)
    sc = Scenario.from_trace(
        tr,
        30 * 3600.0,
        bids=[0.01, 0.30, 0.345, 0.36, 0.40, 5.0],
        schemes=BID_LIMITED_SCHEMES,
        initial_saved_work=10 * 3600.0,
        params=SimParams(t_c=450.0, t_r=900.0),
    )
    assert_parity(sc, engine="jax")


def test_jax_parity_generated_grid_with_fractional_bids():
    """(type x seed x bid x scheme) grid, bids scaled per type's on-demand."""
    types = [it for it in catalog() if it.os == "linux"][:4]
    sc = Scenario.grid(
        work_s=24 * 3600.0,
        bids=[round(0.50 + 0.02 * i, 3) for i in range(4)],
        instances=types,
        schemes=BID_LIMITED_SCHEMES,
        horizon_days=15.0,
        seeds=(0, 1),
        bid_fractions=True,
    )
    report = assert_parity(sc, engine="jax")
    assert report.candidate.engine == "jax"
    assert report.reference.shape == (8, 4, 5)


def test_jax_parity_random_step_traces():
    """Deterministic mini-fuzz: random step traces, params and work sizes."""
    rng = np.random.default_rng(321)
    for trial in range(8):
        n_seg = int(rng.integers(1, 30))
        t = np.sort(rng.uniform(0, 10 * 24 * 3600.0, n_seg - 1)) if n_seg > 1 else np.array([])
        starts = np.concatenate([[0.0], t])
        prices = np.round(rng.uniform(0.05, 1.2, n_seg), 3)
        tr = step_trace(list(zip(starts, prices)), horizon_s=10 * 24 * 3600.0)
        work = float(rng.uniform(600.0, 60 * 3600.0))
        bids = sorted(set(round(float(x), 3) for x in rng.uniform(0.0, 1.3, 4)))
        bp = float(rng.choice([3600.0, 1800.0]))
        params = SimParams(
            t_c=float(rng.uniform(0.0, 0.15) * bp),
            t_r=float(rng.uniform(0.0, 2400.0)),
            billing_period_s=bp,
        )
        init = float(rng.uniform(0, work)) if trial % 3 == 0 else 0.0
        sc = Scenario.from_trace(
            tr, work, bids, schemes=BID_LIMITED_SCHEMES, params=params, initial_saved_work=init
        )
        assert_parity(sc, engine="jax")


def test_jax_acc_falls_back_to_scalar():
    """A full-scheme scenario: ACC runs on the scalar path inside JaxEngine
    (like BatchEngine), every other scheme on the jitted lockstep."""
    tr = synthetic_trace(IT, 20, seed=1)
    sc = Scenario.from_trace(tr, 30 * 3600.0, [0.36, 0.37, 0.38], schemes=tuple(Scheme))
    assert_parity(sc, engine="jax")


def test_run_accepts_jax_engine_name():
    tr = synthetic_trace(IT, 10, seed=2)
    sc = Scenario.from_trace(tr, 5 * 3600.0, [0.36, 0.40], schemes=(Scheme.HOUR, Scheme.ADAPT))
    res = run(sc, engine="jax")
    assert res.engine == "jax"
    ref = run(sc, engine="reference")
    np.testing.assert_array_equal(res.cost, ref.cost)
    np.testing.assert_array_equal(res.completion_time, ref.completion_time)
