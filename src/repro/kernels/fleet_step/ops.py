"""fleet_step op: backend dispatch for the fleet engine's EET scoring waves.

``eet_scores`` evaluates one placement wave's ``(lane, type)`` Eq. 8 matrix:

  * ``"numpy"`` — :func:`repro.kernels.fleet_step.ref.eet_scores_numpy`, the
    bit-exact reference (no jax required; the default).
  * ``"jax"``   — the jitted twin from :func:`.kernel.build_eet_kernel`.
    Lane counts vary per wave (arrivals vs a handful of migrations), so the
    lane axis is padded to a small power-of-two bucket before dispatch: a
    whole fleet grid compiles a handful of programs, and re-running the same
    scenario re-traces nothing (``repro.obs.retrace_guard("fleet_step")``).

Like :mod:`repro.kernels.spot_sweep.ops`, jax is imported lazily — CI's
tier-1 job has no jax and never takes the ``"jax"`` branch.
"""

from __future__ import annotations

import numpy as np

from repro.obs import retrace
from repro.obs import telemetry as obs

#: retrace-registry scope for the jitted EET kernel (detail = padded shape)
TRACE_SCOPE = "fleet_step"

#: jitted kernel per padded (lanes, types) shape; process-wide
_JIT_CACHE: dict[tuple[int, int], object] = {}


def trace_count(shape: tuple[int, int]) -> int:
    """How many times the kernel for padded ``shape`` has been traced."""
    return retrace.trace_count(TRACE_SCOPE, tuple(shape))


def _bucket(n: int) -> int:
    """Pad the lane axis to ``max(8, next power of two)`` so wave sizes that
    wobble between runs reuse one compiled program."""
    b = 8
    while b < n:
        b *= 2
    return b


def _jit_fn(shape, jax_mod):
    fn = _JIT_CACHE.get(shape)
    if fn is None:
        from repro.kernels.fleet_step import kernel as K

        def bump(k=shape):
            retrace.record_trace(TRACE_SCOPE, k)

        fn = jax_mod.jit(K.build_eet_kernel(count_cb=bump))
        _JIT_CACHE[shape] = fn
    return fn


def eet_scores(
    p_fail: np.ndarray,
    wasted: np.ndarray,
    w_scaled: np.ndarray,
    avail: np.ndarray,
    impl: str = "numpy",
) -> np.ndarray:
    """Eq. 8 scores for one ``(lane, type)`` wave; see :mod:`.ref`.

    Each call is a ``fleet.score`` span (attribute ``impl``), from the
    padding to the scores on the host, and counts ``fleet_step.calls``,
    ``fleet_step.lanes`` (the wave's lanes) and ``fleet_step.cells``
    (lanes × types as scored, padded lanes included); the ``"jax"`` impl
    also counts the bytes it copies to the device and back in
    ``fleet_step.h2d_bytes`` / ``fleet_step.d2h_bytes``."""
    if impl not in ("numpy", "jax"):
        raise ValueError(f"unknown fleet_step impl {impl!r}")
    tel = obs.current()
    with tel.span("fleet.score", impl=impl):
        tel.count("fleet_step.calls")
        tel.count("fleet_step.lanes", p_fail.shape[0])
        if impl == "numpy":
            from repro.kernels.fleet_step.ref import eet_scores_numpy

            tel.count("fleet_step.cells", p_fail.size)
            return eet_scores_numpy(p_fail, wasted, w_scaled, avail)
        return _eet_scores_jax(p_fail, wasted, w_scaled, avail, tel)


def _eet_scores_jax(p_fail, wasted, w_scaled, avail, tel) -> np.ndarray:
    """The ``"jax"`` impl: pad the lanes to their bucket, run the jitted
    kernel, copy the scores back and slice the padding off."""
    from repro.engine.jax_backend import _require_jax

    jax_mod, jnp, _ = _require_jax()
    L, T = p_fail.shape
    Lp = _bucket(L)
    if Lp != L:
        pad = ((0, Lp - L), (0, 0))
        p_fail = np.pad(p_fail, pad)
        wasted = np.pad(wasted, pad)
        w_scaled = np.pad(w_scaled, pad)
        avail = np.pad(avail, pad)  # padded lanes: avail False -> inf, sliced off
    fn = _jit_fn((Lp, T), jax_mod)
    args = (p_fail, wasted, w_scaled, avail)
    tel.count("fleet_step.cells", Lp * T)
    tel.count("fleet_step.h2d_bytes", sum(a.nbytes for a in args))
    out = np.asarray(fn(*(jnp.asarray(a) for a in args)))
    tel.count("fleet_step.d2h_bytes", out.nbytes)
    return out[:L]
