"""Shared layer library: norms, RoPE, GQA attention, MLPs, embeddings.

Everything is a pure function over (config, params, activations); parameter
construction lives beside each apply function so init and apply stay in sync.
Logical sharding annotations use repro.parallel.shard (no-ops off-mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as attn_ops
from repro.models.config import ModelConfig
from repro.models.params import ParamBuilder
from repro.parallel import shard

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(b: ParamBuilder, name: str, cfg: ModelConfig, width: int | None = None):
    d = width or cfg.d_model
    if cfg.norm == "rmsnorm":
        b.ones(f"{name}.scale", (d,), ("embed",))
    else:
        b.ones(f"{name}.scale", (d,), ("embed",))
        b.zeros(f"{name}.bias", (d,), ("embed",))


def apply_norm(cfg: ModelConfig, params, name: str, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6)
        return (y * params[f"{name}.scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
    y = y * params[f"{name}.scale"].astype(jnp.float32) + params[f"{name}.bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(cfg: ModelConfig, positions):
    """positions: (...,) int32 -> cos/sin of shape (..., d_head//2)."""
    d = cfg.d_head
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:  # (S, D/2) -> broadcast over batch and heads
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:  # (B, S, D/2)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, optional RoPE / learned positions)
# ---------------------------------------------------------------------------


def init_attention(b: ParamBuilder, name: str, cfg: ModelConfig):
    # "fsdp" on the non-TP dim: ZeRO-3 sharding over (pod, data); XLA inserts
    # the all-gather-on-use / reduce-scatter-on-grad pattern from the sharding
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    b.dense(f"{name}.wq", (d, h, dh), ("fsdp", "heads", "head_dim"))
    b.dense(f"{name}.wk", (d, kv, dh), ("fsdp", "kv_heads", "head_dim"))
    b.dense(f"{name}.wv", (d, kv, dh), ("fsdp", "kv_heads", "head_dim"))
    b.dense(f"{name}.wo", (h, dh, d), ("heads", "head_dim", "fsdp"))


def _qkv(cfg: ModelConfig, params, name: str, x, positions=None):
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{name}.wq"])
    k = jnp.einsum("bsd,dke->bske", x, params[f"{name}.wk"])
    v = jnp.einsum("bsd,dke->bske", x, params[f"{name}.wv"])
    if not cfg.learned_pos and positions is not None:
        cos, sin = rope_frequencies(cfg, positions)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def apply_attention(
    cfg: ModelConfig,
    params,
    name: str,
    x,
    *,
    causal=True,
    window=0,
    q_block=1024,
    kv_block=1024,
):
    """Full-sequence (train/prefill) attention.  Returns (out, (k, v))."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _qkv(cfg, params, name, x, positions)
    o = attn_ops.flash_attention(
        q, k, v, causal=causal, window=window, q_block=q_block, kv_block=kv_block
    )
    out = jnp.einsum("bshe,hed->bsd", o, params[f"{name}.wo"])
    return shard(out, "batch", "seq", "embed"), (k, v)


def apply_attention_decode(cfg: ModelConfig, params, name: str, x, cache, *, window=0):
    """One-token decode.  cache: dict(k=(B,S_c,KV,D), v=..., len=scalar int32).

    If the cache is window-sized (S_c <= window), it is treated as a
    *circular* buffer: the new token writes at ``len % S_c`` and every slot
    holds one of the most recent S_c positions — RoPE keys carry absolute
    positions, so attention scores stay correct after wrap-around.
    """
    b, one, _ = x.shape
    pos = cache["len"]  # scalar int32: current length before append
    s_c = cache["k"].shape[1]
    circular = bool(window) and s_c <= window
    q = jnp.einsum("bsd,dhe->bshe", x, params[f"{name}.wq"])
    k_new = jnp.einsum("bsd,dke->bske", x, params[f"{name}.wk"])
    v_new = jnp.einsum("bsd,dke->bske", x, params[f"{name}.wv"])
    if not cfg.learned_pos:
        cos, sin = rope_frequencies(cfg, pos[None])
        q = apply_rope(q, cos[None], sin[None])
        k_new = apply_rope(k_new, cos[None], sin[None])
    # SP path: sequence-sharded cache + distributed flash-decoding merge
    from repro.parallel.sharding import current_rules
    from repro.parallel import sp_decode

    if (
        not circular
        and current_rules().get("kv_seq") == "model"
        and sp_decode.sp_available(s_c)
    ):
        mesh = jax.sharding.get_abstract_mesh()
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        data_prod = 1
        for a in ("pod", "data"):
            data_prod *= sizes.get(a, 1)
        o, k_cache, v_cache = sp_decode.sp_decode_attention_update(
            q, k_new, v_new, cache["k"], cache["v"], pos, batch_divisible=True
        )
        out = jnp.einsum("bshe,hed->bsd", o, params[f"{name}.wo"])
        return shard(out, "batch", "seq", "embed"), {"k": k_cache, "v": v_cache, "len": pos + 1}
    write_at = jnp.mod(pos, s_c) if circular else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), write_at, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), write_at, axis=1)
    k_cache = shard(k_cache, "batch", "kv_seq", "kv_heads", "head_dim")
    v_cache = shard(v_cache, "batch", "kv_seq", "kv_heads", "head_dim")
    cur = jnp.minimum(pos + 1, s_c) if circular else pos + 1
    o = attn_ops.decode_attention(q, k_cache, v_cache, cur, window=0 if circular else window)
    out = jnp.einsum("bshe,hed->bsd", o, params[f"{name}.wo"])
    new_cache = {"k": k_cache, "v": v_cache, "len": pos + 1}
    return shard(out, "batch", "seq", "embed"), new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    kv, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((batch, max_len, kv, dh), dtype),
        "v": jnp.zeros((batch, max_len, kv, dh), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def attention_cache_axes():
    return {
        "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "len": (),
    }


# ---------------------------------------------------------------------------
# MLP (gated GLU or plain)
# ---------------------------------------------------------------------------


def init_mlp(b: ParamBuilder, name: str, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.gated_mlp:
        b.dense(f"{name}.wi_gate", (d, f), ("fsdp", "mlp"))
        b.dense(f"{name}.wi_up", (d, f), ("fsdp", "mlp"))
    else:
        b.dense(f"{name}.wi_up", (d, f), ("fsdp", "mlp"))
    b.dense(f"{name}.wo", (f, d), ("mlp", "fsdp"))


def _act(cfg: ModelConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def apply_mlp(cfg: ModelConfig, params, name: str, x):
    up = jnp.einsum("bsd,df->bsf", x, params[f"{name}.wi_up"])
    if cfg.gated_mlp:
        gate = jnp.einsum("bsd,df->bsf", x, params[f"{name}.wi_gate"])
        h = _act(cfg, gate) * up
    else:
        h = _act(cfg, up)
    h = shard(h, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, params[f"{name}.wo"])
    return shard(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(b: ParamBuilder, cfg: ModelConfig):
    # vocab padded to a TPU-friendly multiple (MaxText-style): padded ids are
    # never label targets, so their logits only add (trainable-away) softmax mass
    v = cfg.padded_vocab
    b.dense("embed.tokens", (v, cfg.d_model), ("vocab", "embed"), scale=1.0)
    if cfg.learned_pos:
        b.dense("embed.positions", (cfg.max_position, cfg.d_model), (None, "embed"), scale=0.02)
    if not cfg.tie_embeddings:
        b.dense("unembed", (cfg.d_model, v), ("embed", "vocab"))


def embed_tokens(cfg: ModelConfig, params, tokens, position_offset=0):
    x = jnp.take(params["embed.tokens"], tokens, axis=0)
    if cfg.learned_pos:
        pos = jnp.arange(tokens.shape[1]) + position_offset
        x = x + jnp.take(params["embed.positions"], pos, axis=0)[None]
    return shard(x, "batch", "seq", "embed")


def unembed(cfg: ModelConfig, params, x):
    w = params["embed.tokens"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return shard(logits, "batch", "seq", "vocab")
