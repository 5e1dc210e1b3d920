"""Public attention op with backend dispatch.

  * TPU          -> Pallas flash kernel (kernel.py)
  * tests        -> Pallas kernel in interpret mode (validated vs ref)
  * CPU/dry-run  -> block_attention ref (same tiling; exact cost accounting)
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import ref


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 1024,
    kv_block: int = 1024,
    q_offset: int = 0,
    impl: str | None = None,
):
    impl = impl or ("pallas" if jax.default_backend() == "tpu" else "ref")
    sq, sk = q.shape[1], k.shape[1]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    if impl in ("pallas", "interpret"):
        from repro.kernels.flash_attention import kernel

        return kernel.flash_attention_tpu(
            q,
            k,
            v,
            causal=causal,
            window=window,
            q_block=q_block,
            kv_block=kv_block,
            q_offset=q_offset,
            interpret=impl == "interpret",
        )
    return ref.block_attention(
        q, k, v, causal=causal, window=window, q_block=q_block, kv_block=kv_block, q_offset=q_offset
    )


decode_attention = ref.decode_attention
