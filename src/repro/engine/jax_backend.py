"""JAX backends: the fused spot-sweep programs on the (type × bid × seed) grid.

:class:`JaxEngine` evaluates every batched scheme of a scenario as **one**
jit-compiled program: the multi-scheme ``lax.scan`` built by
:mod:`repro.kernels.spot_sweep` walks the padded period axis once, advancing
each scheme's state segment inside the same period step (scheme is a static
segment axis of the trace, not five separate jits), with
``lax.while_loop`` for checkpoint-window / ADAPT decision ticks.  The
billing inputs — per-period run records and the ``n_kills`` tally —
accumulate on-device in the scan carry/ys; the host only folds the records
through the vectorized NumPy biller shared with
:class:`~repro.engine.batch.BatchEngine`.

:class:`PallasEngine` runs the same step as the fused Pallas kernel
(``repro.kernels.spot_sweep.kernel.sweep_pallas``) in interpreter mode, and
only when the caller asks for it (``PallasEngine(interpret=True)``): the
float64 kernel does not compile natively for the TPU
(:data:`~repro.kernels.spot_sweep.kernel.NATIVE_UNSUPPORTED`), so a native
``PallasEngine`` raises instead of interpreting behind the caller's back.

The per-step float expressions are the shared pure kernels of
:mod:`repro.engine.kernels` called with ``xp=jax.numpy`` (x64 enabled):
elementwise float64 ops are IEEE-exact on CPU, so every program produces the
same bit patterns as the NumPy driver and the scalar reference, and
:mod:`repro.engine.parity` asserts ``==`` across all of them.  A TPU has no
float64 unit: XLA emulates it with about 49 mantissa bits and the float32
exponent range, so there the program agrees with the NumPy driver only where
every value and operation is exact in that format (``chip_smoke.py`` checks
such a grid ``==`` and prints the full-catalog disagreement), and
``engine="auto"`` stays on the batch backend.  A missing JAX raises
:class:`ImportError` with an install hint instead of silently changing
substrates.

:func:`_require_jax` is the one place the device programs configure JAX:
float64 (the parity substrate) and the persistent compilation cache, kept
where ``JAX_COMPILATION_CACHE_DIR`` says or, when that is unset and the
default backend is an accelerator, at the fixed :data:`DEFAULT_CACHE_DIR`
inside the source checkout (none when the package is installed elsewhere).
"""

from __future__ import annotations

import pathlib

from repro.engine.base import EngineResult
from repro.engine.batch import run_batched
from repro.engine.scenario import Scenario


def _checkout_cache_dir() -> pathlib.Path | None:
    """``<checkout>/.jax_cache`` when this package runs from its source
    checkout (``src/repro`` beside the project's ``pyproject.toml``), else
    None: an installed package has no checkout to keep a cache in."""
    root = pathlib.Path(__file__).resolve().parents[3]
    if (root / "pyproject.toml").is_file() and (root / "src" / "repro").is_dir():
        return root / ".jax_cache"
    return None


#: compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path (the path is part of the cache key, so it must not move)
DEFAULT_CACHE_DIR = _checkout_cache_dir()


def have_jax() -> bool:
    """True when a working jax is importable (used by tests/CI to skip)."""
    try:
        import jax  # noqa: F401
    except Exception:
        return False
    return True


def _require_jax():
    try:
        import jax
        import jax.numpy as jnp
        from jax import lax
    except ImportError as e:  # pragma: no cover - exercised only without jax
        raise ImportError(
            "the 'jax' engine backend requires jax (CPU wheels suffice: "
            "pip install jax); pick engine='batch' for the NumPy backend"
        ) from e
    jax.config.update("jax_enable_x64", True)  # float64 parity is the contract
    # env var unset: keep accelerator programs at the fixed default.  XLA:CPU
    # entries are tied to the host's instruction set, so CPU keeps none.
    if (
        jax.config.jax_compilation_cache_dir is None
        and DEFAULT_CACHE_DIR is not None
        and jax.default_backend() != "cpu"
    ):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax, jnp, lax


class JaxEngine:
    """One-compile multi-scheme evaluation; on CPU bit-identical to the
    reference/batch backends on cost / completion_time / n_kills /
    n_checkpoints for every batched scheme (on a TPU, see the module notes
    on emulated float64).  The compiled program is cached
    per scheme set (module-level, shared by every engine instance in the
    process) and keyed only on grid *shape* — re-running a same-shape
    scenario never retraces (``tests/engine/test_engine_caches.py`` spies on
    the trace count)."""

    name = "jax"
    #: which spot_sweep implementation this engine requests
    impl: str = "scan"

    def __init__(self):
        self._jax, self._jnp, self._lax = _require_jax()

    def run(self, scenario: Scenario) -> EngineResult:
        return run_batched(scenario, self.name, self._run_schemes)

    def _run_schemes(self, schemes, grid, scenario, adapt_tables):
        from repro.kernels.spot_sweep import ops as sweep_ops

        return sweep_ops.spot_sweep_grid(
            schemes, grid, scenario, adapt_tables, impl=self.impl
        )


class PallasEngine(JaxEngine):
    """The fused Pallas lockstep kernel as an engine backend.

    Interpreter mode must be asked for (``interpret=True``): exact, but
    orders of magnitude slower than the jitted scan, so it is meant for
    parity verification and kernel development, not throughput.  Without it
    the engine would have to compile the kernel natively, which the float64
    kernel cannot do on the TPU, so construction raises
    :class:`NotImplementedError` naming why (porting the kernel off float64
    is tracked in ROADMAP.md)."""

    name = "pallas"

    def __init__(self, interpret: bool = False):
        if not interpret:
            from repro.kernels.spot_sweep.kernel import NATIVE_UNSUPPORTED

            raise NotImplementedError(NATIVE_UNSUPPORTED)
        super().__init__()
        self.impl = "interpret"
