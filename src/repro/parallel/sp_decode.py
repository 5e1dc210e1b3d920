"""Sequence-parallel (SP) decode attention: distributed flash-decoding.

For decode shapes whose KV cache is sequence-sharded over "model"
(rules["kv_seq"] == "model"), the annotation-only version lets the SPMD
partitioner all-gather the cache every layer (measured: +96 all-gathers,
23x wire bytes on internlm2 decode_32k — §Perf iter 1).  This shard_map
version computes the online-softmax partials (m, l, o) on each rank's local
KV slice and combines with pmax/psum — wire cost per layer drops from
O(B*S*KV*D) to O(B*H*D).

Also handles the cache append: only the rank owning slot ``pos`` writes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def sp_available(s_c: int) -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return False
    tp = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
    return s_c % tp == 0


def sp_decode_attention_update(q, k_new, v_new, k_cache, v_cache, pos, batch_divisible: bool):
    """q: (B,1,H,D); k_new/v_new: (B,1,KV,D); caches (B,S,KV,D) seq-sharded.

    Returns (out (B,1,H,D), new_k, new_v).  ``pos``: scalar int32 append slot.
    """
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    tp = sizes["model"]
    b, _, h, d = q.shape
    _, s_c, n_kv, _ = k_cache.shape
    s_loc = s_c // tp
    g = h // n_kv

    batch_axes = [a for a in ("pod", "data") if a in sizes]
    prod = 1
    kept = []
    for a in batch_axes:
        if batch_divisible and b % (prod * sizes[a]) == 0:
            kept.append(a)
            prod *= sizes[a]
    bspec = tuple(kept) if len(kept) > 1 else (kept[0] if kept else None)

    def shard_fn(q_blk, kn, vn, kc, vc, pos_s):
        rank = jax.lax.axis_index("model")
        # --- append: only the owning rank writes slot pos ------------------
        local = pos_s - rank * s_loc
        owner = (local >= 0) & (local < s_loc)
        idx = jnp.clip(local, 0, s_loc - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(kc, idx, 1, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, idx, 1, axis=1)
        upd_k = jnp.where(owner, kn.astype(kc.dtype), cur_k)
        upd_v = jnp.where(owner, vn.astype(vc.dtype), cur_v)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, upd_k, idx, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, upd_v, idx, axis=1)

        # --- local partial attention ---------------------------------------
        qg = q_blk.reshape(q_blk.shape[0], n_kv, g, d).astype(jnp.float32)
        s = jnp.einsum("bkgd,bckd->bkgc", qg, kc.astype(jnp.float32)) * (1.0 / math.sqrt(d))
        pos_abs = rank * s_loc + jnp.arange(s_loc)
        mask = pos_abs[None, :] < (pos_s + 1)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)  # (b,k,g)
        p = jnp.exp(s - m_loc[..., None])
        p = jnp.where(mask[:, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, axis=-1)
        o_loc = jnp.einsum("bkgc,bckd->bkgd", p, vc.astype(jnp.float32))

        # --- combine across ranks (flash-decoding merge) -------------------
        m_glob = jax.lax.pmax(m_loc, "model")
        alpha = jnp.exp(m_loc - m_glob)
        l_glob = jax.lax.psum(l_loc * alpha, "model")
        o_glob = jax.lax.psum(o_loc * alpha[..., None], "model")
        out = (o_glob / jnp.maximum(l_glob, 1e-37)[..., None]).reshape(q_blk.shape[0], 1, h, d)
        return out.astype(q_blk.dtype), kc, vc

    out, new_k, new_v = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),  # q replicated over model
            P(bspec, None, None, None),
            P(bspec, None, None, None),
            P(bspec, "model", None, None),  # seq-sharded caches
            P(bspec, "model", None, None),
            P(),
        ),
        out_specs=(
            P(bspec, None, None, None),
            P(bspec, "model", None, None),
            P(bspec, "model", None, None),
        ),
    )(q, k_new, v_new, k_cache, v_cache, pos)
    return out, new_k, new_v
