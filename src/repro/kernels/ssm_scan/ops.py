"""SSM scan op with backend dispatch (pallas on TPU, associative-scan ref
elsewhere)."""

from __future__ import annotations

import jax

from repro.kernels.ssm_scan import ref


def ssm_scan(dtA, dBx, C, h0=None, *, chunk: int = 256, impl: str | None = None):
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "ref")
    if impl in ("pallas", "interpret"):
        from repro.kernels.ssm_scan import kernel

        return kernel.ssm_scan_tpu(dtA, dBx, C, h0, chunk=chunk, interpret=impl == "interpret")
    return ref.ssm_scan(dtA, dBx, C, h0)


ssm_step = ref.ssm_step
linear_scan = ref.linear_scan
