"""RG-LRU scan op with backend dispatch."""

from __future__ import annotations

import jax

from repro.kernels.rglru_scan import ref


def rglru_scan(log_a, gated_x, h0=None, *, chunk: int = 256, impl: str | None = None):
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "ref")
    if impl in ("pallas", "interpret"):
        from repro.kernels.rglru_scan import kernel

        return kernel.rglru_scan_tpu(log_a, gated_x, h0, chunk=chunk, interpret=impl == "interpret")
    return ref.rglru_scan(log_a, gated_x, h0)


rglru_step = ref.rglru_step
