"""Expert-parallel MoE via shard_map (hillclimb: collective-optimal dispatch).

The annotation-based dispatch in moe.py scatters tokens into a globally
(batch, experts, capacity, d) buffer and lets the SPMD partitioner pick the
collectives; measured on kimi-k2 train_4k it picks catastrophically
(~1.6e14 wire bytes/device/step — §Perf).  This module expresses the same
math with *explicit* locality:

  * activations are replicated along "model" (they already are: batch is
    data-sharded, d unsharded), so routing is computed redundantly per rank
    — zero communication;
  * each model rank gathers ONLY the tokens routed to its E/tp local
    experts (local gather), runs its expert FFNs, scatters results into a
    local (B, S, d) buffer;
  * one psum over "model" combines expert outputs — the same wire cost as
    a dense TP FFN's all-reduce.

Per layer the collective traffic drops from O(B*E*C*d) to O(B*S*d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.moe import moe_capacity
from repro.parallel.sharding import current_rules


def _mesh_for_ep():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return None
    return mesh


def apply_moe_ep(cfg: ModelConfig, params, name: str, x):
    """Drop-in replacement for moe.apply_moe; falls back to it off-mesh."""
    mesh = _mesh_for_ep()
    if mesh is None:
        from repro.models.moe import apply_moe

        return apply_moe(cfg, params, name, x)

    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    tp = sizes["model"]
    e, k = cfg.n_experts, cfg.top_k
    if e % tp != 0:
        from repro.models.moe import apply_moe

        return apply_moe(cfg, params, name, x)
    e_loc = e // tp
    bsz, s, d = x.shape
    c = moe_capacity(cfg, s)
    tk = s * k

    batch_axes = tuple(a for a in ("pod", "data") if a in sizes and bsz % sizes[a] == 0)
    # batch divisibility across the full product
    prod = 1
    kept = []
    for a in batch_axes:
        if bsz % (prod * sizes[a]) == 0:
            kept.append(a)
            prod *= sizes[a]
    bspec = tuple(kept) if len(kept) > 1 else (kept[0] if kept else None)

    wi_up = params[f"{name}.wi_up"]
    wo = params[f"{name}.wo"]
    router = params[f"{name}.router"]
    wi_gate = params[f"{name}.wi_gate"] if cfg.gated_mlp else None

    def shard_fn(x_blk, router_w, wi_up_l, wo_l, *maybe_gate):
        wi_gate_l = maybe_gate[0] if maybe_gate else None
        b_loc = x_blk.shape[0]
        rank = jax.lax.axis_index("model")
        logits = jnp.einsum("bsd,de->bse", x_blk, router_w).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)  # identical on every model rank
        top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

        eid = top_e.reshape(b_loc, tk)
        owned = (eid // e_loc) == rank
        local_e = jnp.where(owned, eid % e_loc, e_loc)  # e_loc = overflow bucket
        sort_idx = jnp.argsort(local_e, axis=1, stable=True)
        sorted_e = jnp.take_along_axis(local_e, sort_idx, axis=1)
        counts = jnp.zeros((b_loc, e_loc + 1), jnp.int32).at[
            jnp.arange(b_loc)[:, None], local_e
        ].add(1)
        offsets = jnp.cumsum(counts, axis=1) - counts
        pos = jnp.arange(tk)[None, :] - jnp.take_along_axis(offsets, sorted_e, axis=1)
        keep = (sorted_e < e_loc) & (pos < c)
        pos = jnp.minimum(pos, c - 1)
        slot_e = jnp.minimum(sorted_e, e_loc - 1)

        brange = jnp.arange(b_loc)[:, None]
        tok = sort_idx // k
        gathered = x_blk[brange, tok] * keep[..., None].astype(x_blk.dtype)
        buf = jnp.zeros((b_loc, e_loc, c, d), x_blk.dtype).at[brange, slot_e, pos].add(gathered)

        up = jnp.einsum("becd,edf->becf", buf, wi_up_l)
        if wi_gate_l is not None:
            h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, wi_gate_l)) * up
        else:
            h = jax.nn.gelu(up)
        out_buf = jnp.einsum("becf,efd->becd", h, wo_l)

        back = out_buf[brange, slot_e, pos] * keep[..., None].astype(x_blk.dtype)
        w_sorted = jnp.take_along_axis(top_w.reshape(b_loc, tk), sort_idx, axis=1)
        back = back * w_sorted[..., None].astype(x_blk.dtype)
        y = jnp.zeros((b_loc, s, d), x_blk.dtype).at[brange, tok].add(back)
        y = jax.lax.psum(y, "model")

        # aux (replicated along model; mean over the data axes)
        frac_tokens = jnp.zeros((b_loc, e), jnp.float32).at[brange, eid].add(1.0) / tk
        lb = e * jnp.mean(jnp.sum(frac_tokens * jnp.mean(probs, axis=1), axis=-1))
        kept_n = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), "model")
        drop = 1.0 - kept_n / (b_loc * tk)
        if kept:
            lb = jax.lax.pmean(lb, tuple(kept))
            drop = jax.lax.pmean(drop, tuple(kept))
        return y, lb, drop

    in_specs = [
        P(bspec, None, None),  # x: replicated along model
        P(None, None),  # router
        P("model", None, None),  # expert weights: E sharded
        P("model", None, None),
    ]
    args = [x, router, wi_up, wo]
    if wi_gate is not None:
        in_specs.append(P("model", None, None))
        args.append(wi_gate)
    out_specs = (P(bspec, None, None), P(), P())
    y, lb, drop = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs
    )(*args)
    return y, {"load_balance_loss": lb, "drop_frac": drop}
