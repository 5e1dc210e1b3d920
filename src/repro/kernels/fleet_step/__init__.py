"""fleet_step kernel triad: the fleet engine's per-wave EET scoring op.

Its files:

  * ``ref.py``    — NumPy reference (`eet_scores_numpy`), bit-exact vs the
    scalar :func:`repro.core.provision.expected_execution_time` combine;
  * ``kernel.py`` — the jittable JAX twin (built via ``build_eet_kernel``);
  * ``ops.py``    — backend dispatch (``eet_scores``) with the jit cache and
    retrace accounting (scope ``"fleet_step"``).
"""

from repro.kernels.fleet_step.ops import eet_scores, trace_count

__all__ = ["eet_scores", "trace_count"]
