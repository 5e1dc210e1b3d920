"""Checkpoint codec op with backend dispatch (pallas on TPU, jnp elsewhere)."""

from __future__ import annotations

import jax

from repro.kernels.ckpt_codec import ref


def quantize(x, block: int = ref.BLOCK, impl: str | None = None):
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "ref")
    if impl in ("pallas", "interpret"):
        from repro.kernels.ckpt_codec import kernel

        return kernel.quantize_tpu(x, block=block, interpret=impl == "interpret")
    return ref.quantize(x, block)


dequantize = ref.dequantize
