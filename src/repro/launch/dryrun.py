import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (device count is now locked) -----------

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
import warnings  # noqa: E402

warnings.filterwarnings("ignore")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, applicable, batch_specs  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    DEFAULT_RULES,
    axis_rules,
    logical_sharding,
    shard_params,
)
from repro.train.steps import make_train_step  # noqa: E402

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, lower + compile the real step
function (train_step / prefill / serve_step) against the production mesh —
16x16 single-pod and 2x16x16 multi-pod — with ShapeDtypeStruct inputs (no
allocation), then record:

  * compiled.memory_analysis()  (bytes per device: proves it fits / or not)
  * compiled.cost_analysis()    (per-device HLO FLOPs and bytes)
  * the collective schedule parsed from compiled HLO text (op kind, shape,
    ring-model wire bytes)

Results land in results/dryrun/<arch>__<shape>__<mesh>.json; §Roofline reads
them.  All sequential structure in the models is Python-unrolled
(DESIGN.md §Analysis), so cost_analysis is trip-count-exact.
"""

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\][^=]*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_GROUP_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def parse_collectives(hlo_text: str, default_group: int) -> dict:
    """Aggregate collective ops: count + ring-model wire bytes per chip.

    Wire-byte model (ring): all-reduce 2(n-1)/n * B; all-gather (n-1)/n * B_out;
    reduce-scatter (n-1)/n * B_in (= n * B_out); all-to-all (n-1)/n * B;
    collective-permute B.
    """
    agg: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        size = _DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                size *= int(d)
        g = default_group
        gm = _GROUP_RE.search(line)
        if gm:
            g = max(len(gm.group(1).split(",")), 1)
        else:
            gm2 = _GROUP_RE2.search(line)
            if gm2:
                g = max(int(gm2.group(2)), 1)
        n = max(g, 2)
        if kind == "all-reduce":
            wire = 2 * (n - 1) / n * size
        elif kind == "all-gather":
            wire = (n - 1) / n * size  # size = result (gathered)
        elif kind == "reduce-scatter":
            wire = (n - 1) * size  # size = result (scattered piece)
        elif kind == "all-to-all":
            wire = (n - 1) / n * size
        else:  # collective-permute
            wire = size
        a = agg.setdefault(kind, {"count": 0, "bytes": 0.0, "wire_bytes": 0.0})
        a["count"] += 1
        a["bytes"] += size
        a["wire_bytes"] += wire
    return agg


def _mem_dict(mem) -> dict:
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

# blocks tuned per shape: one q-block for train (exact causal via 3 tiles),
# 4096-tiles for the 32k prefill (36 visible tiles)
_BLOCKS = {"train_4k": (2048, 2048), "prefill_32k": (4096, 4096), "decode_32k": None, "long_500k": None}


def _opt_cfg(cfg) -> AdamWConfig:
    # bf16 moments for the >=100B models (memory table in EXPERIMENTS.md)
    big = cfg.param_count() > 100e9
    return AdamWConfig(moment_dtype="bfloat16" if big else "float32")


def build_cell(arch: str, shape_name: str, mesh, rules=None, variant: str = "baseline"):
    """Returns (jitted_fn, abstract_args) for the cell.

    Variants (§Perf hillclimb):
      ep_moe — shard_map expert-parallel MoE dispatch (moe archs)
      sp_kv  — sequence-sharded KV cache for decode shapes
    """
    cfg = get_config(arch)
    if variant == "ep_moe":
        cfg = dataclasses.replace(cfg, moe_impl="ep")
    rules = rules or DEFAULT_RULES
    if variant == "sp_kv":
        rules = {**rules, "kv_seq": "model"}
    spec = SHAPES[shape_name]
    params_abs = T.abstract_params(cfg)
    axes = T.param_axes(cfg)
    params_sh = shard_params(mesh, axes, rules, abstract_tree=params_abs)
    batch_abs = batch_specs(cfg, shape_name)

    def batch_shardings():
        out = {}
        for k, v in batch_abs.items():
            if k in ("tokens", "labels", "vision_mask"):
                logical = ("batch", "seq")
            elif k == "vision_embeds":
                logical = ("batch", None, "embed")
            elif k == "frames":
                logical = ("batch", None, "embed")
            else:
                logical = tuple([None] * v.ndim)
            # batch=1 (long_500k) cannot shard over 32 data shards
            out[k] = logical_sharding(mesh, logical, rules, tuple(v.shape))
        return out

    if spec.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)
        from repro.optim.adamw import opt_state_axes

        opt_sh = shard_params(mesh, opt_state_axes(axes), rules, abstract_tree=opt_abs)
        opt_sh["step"] = logical_sharding(mesh, (), rules)
        qb, kb = _BLOCKS[shape_name]
        step = make_train_step(cfg, opt_cfg, remat=True, q_block=qb, kv_block=kb)
        fn = jax.jit(
            step,
            in_shardings=(params_sh, opt_sh, batch_shardings()),
            out_shardings=(params_sh, opt_sh, None),
        )
        return fn, (params_abs, opt_abs, batch_abs), rules

    if spec.kind == "prefill":
        qb, kb = _BLOCKS[shape_name]

        def prefill_fn(params, batch):
            return T.prefill(cfg, params, batch, max_len=spec.seq_len, q_block=qb, kv_block=kb)

        fn = jax.jit(prefill_fn, in_shardings=(params_sh, batch_shardings()))
        return fn, (params_abs, batch_abs), rules

    # decode: serve_step over a seq_len cache
    bsz = spec.global_batch
    cache_abs = jax.eval_shape(
        lambda: T.init_cache(cfg, bsz, spec.seq_len, jnp.bfloat16)
    )
    cache_rules = dict(rules)
    if bsz % _axis_size(mesh, rules.get("batch")) != 0:
        cache_rules["batch"] = None
    if shape_name == "long_500k":
        cache_rules["kv_seq"] = None  # window caches are small; state is TP-sharded
    cache_sh = shard_params(mesh, T.cache_axes(cfg), cache_rules, abstract_tree=cache_abs)
    tok_sh = logical_sharding(mesh, ("batch", None), cache_rules)

    def decode_fn(params, tokens, cache):
        return T.decode_step(cfg, params, tokens, cache)

    fn = jax.jit(decode_fn, in_shardings=(params_sh, tok_sh, cache_sh))
    tok_abs = batch_abs["tokens"]
    return fn, (params_abs, tok_abs, cache_abs), cache_rules


def _axis_size(mesh, target) -> int:
    if target is None:
        return 1
    if isinstance(target, str):
        target = (target,)
    n = 1
    for t in target:
        if t in mesh.axis_names:
            n *= mesh.devices.shape[mesh.axis_names.index(t)]
    return n


# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str, variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    ok, why = applicable(cfg, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "status": "skipped" if not ok else "pending",
    }
    if not ok:
        record["skip_reason"] = why
        _write(out_dir, record)
        return record
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chip_count(mesh)
    try:
        with jax.sharding.set_mesh(mesh):
            t0 = time.time()
            fn, args, used_rules = build_cell(arch, shape_name, mesh, variant=variant)
            with axis_rules(used_rules):
                lowered = fn.lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            # opt-level 0: 2.6x faster CPU compile, identical cost stats.
            # NOTE (DESIGN.md §Analysis): XLA:CPU CSEs jax.checkpoint's
            # recompute away at ANY opt level, so temp_bytes reports the
            # no-remat footprint; the roofline module adds the analytic
            # remat-corrected activation estimate for the TPU target.
            compiled = lowered.compile(compiler_options={"xla_backend_optimization_level": 0})
            t_compile = time.time() - t0
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            colls = parse_collectives(compiled.as_text(), default_group=chips)
        record.update(
            status="ok",
            chips=chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=_mem_dict(mem),
            flops_per_device=cost.get("flops", 0.0),
            bytes_per_device=cost.get("bytes accessed", 0.0),
            transcendentals=cost.get("transcendentals", 0.0),
            collectives=colls,
            model_params=cfg.param_count(),
            model_active_params=cfg.active_param_count(),
        )
    except Exception as e:  # record the failure: dry-run failures are bugs
        record.update(status="error", error=f"{type(e).__name__}: {e}", trace=traceback.format_exc()[-2000:])
    _write(out_dir, record)
    return record


def _write(out_dir: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if record.get("variant", "baseline") == "baseline" else f"__{record['variant']}"
    path = os.path.join(out_dir, f"{record['arch']}__{record['shape']}__{record['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="baseline", choices=["baseline", "ep_moe", "sp_kv"])
    args = ap.parse_args()
    archs = ARCH_IDS if args.arch == "all" else (args.arch,)
    shapes = tuple(SHAPES) if args.shape == "all" else (args.shape,)
    meshes = {"single": (False,), "multi": (True,), "both": (False, True)}[args.mesh]
    t00 = time.time()
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, multi, args.out, variant=args.variant)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    gb = rec["memory"]["argument_bytes"] / 2**30
                    extra = f" args={gb:.2f}GiB/dev flops={rec['flops_per_device']:.3g}"
                elif status == "error":
                    extra = " " + rec["error"][:120]
                print(
                    f"[{time.time()-t00:7.1f}s] {arch:18s} {shape:12s} "
                    f"{'multi' if multi else 'single':6s} -> {status}{extra} ({time.time()-t0:.1f}s)",
                    flush=True,
                )


if __name__ == "__main__":
    main()
