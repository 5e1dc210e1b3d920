"""The scenario main path's device programs compile for a TPU v5e.

Each test compiles one program at its real size for a v5e chip that is
described, not attached (``jax.experimental.topologies``), so what the chip's
compiler would refuse fails here at no chip time.  Nothing executes: a
compile that passes says nothing about results or speed.

* the fused spot-sweep scan (``engine="jax"``) at the full-catalog study
  grid of ``benchmarks/engine_bench.py`` ``full_scenario()``;
* the fleet EET scoring kernel (``run_fleet(..., engine="jax")``) at a
  padded ``(256, 32)`` float64 wave.

The topology is described inside a fixture, never at import, so that every
test worker collects the same tests and only the one that runs this file
loads the TPU compiler.
"""

from __future__ import annotations

import importlib.util
import pathlib

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

#: device memory of one v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs outside the checkout
        from jax.experimental import topologies

        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _full_scenario():
    spec = importlib.util.spec_from_file_location(
        "engine_bench", pathlib.Path(__file__).parents[2] / "benchmarks" / "engine_bench.py"
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench.full_scenario()


def _on_chip(x, sharding):
    """Abstract argument on the described chip: arrays by shape and dtype,
    Python scalars weakly typed, as ``jit`` sees them."""
    if isinstance(x, (int, float)):
        return jax.ShapeDtypeStruct((), jnp.result_type(x), weak_type=True, sharding=sharding)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert 0 < used < V5E_HBM_BYTES, used


def test_sweep_scan_compiles_at_full_catalog(one_chip, no_persistent_cache):
    from repro.core import Scheme
    from repro.engine.batch import grid_and_tables
    from repro.engine.jax_backend import _require_jax
    from repro.kernels.spot_sweep import kernel as K
    from repro.kernels.spot_sweep import ops

    _require_jax()  # float64 on, as the engine runs it
    scenario = _full_scenario()
    schemes = tuple(scenario.schemes)
    need_edge, need_adapt = Scheme.EDGE in schemes, Scheme.ADAPT in schemes
    assert need_edge and need_adapt and Scheme.ACC not in schemes
    grid, tables = grid_and_tables(scenario, scenario.materialize(), need_adapt)
    assert grid.n_cells == 64 * 41 * 4
    kwargs = ops.scan_arrays(grid, need_edge, need_adapt, scenario.params.t_r, tables)
    kwargs.update(ops.scan_scalars(scenario, need_adapt, tables))
    args = {k: _on_chip(v, one_chip) for k, v in kwargs.items()}
    compiled = jax.jit(K.build_sweep_scan(schemes)).lower(**args).compile()
    _fits_one_chip(compiled)


def test_fleet_eet_kernel_compiles(one_chip, no_persistent_cache):
    from repro.engine.jax_backend import _require_jax
    from repro.kernels.fleet_step import kernel as K

    _require_jax()
    f64 = jax.ShapeDtypeStruct((256, 32), jnp.float64, sharding=one_chip)
    avail = jax.ShapeDtypeStruct((256, 32), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(K.build_eet_kernel()).lower(f64, f64, f64, avail).compile()
    _fits_one_chip(compiled)
