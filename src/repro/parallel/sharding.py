"""Logical-axis sharding: flax-style rules mapping logical names to mesh axes.

Model code annotates tensors with *logical* axes (``("batch","seq","embed")``)
and never mentions the mesh.  A rule set maps logical -> mesh axes; inside an
active mesh, :func:`shard` becomes ``with_sharding_constraint`` and
:func:`logical_sharding` builds ``NamedSharding`` for jit in/out shardings.
Outside a mesh everything is a no-op, so single-device smoke tests run the
same code path.

Parallelism styles expressed purely through rules (DESIGN.md §5):

  * DP/FSDP  — "batch" and the designated fsdp param axis -> ("pod","data")
  * TP       — "heads"/"mlp"/"vocab"/"kv_heads" -> "model"
  * EP       — "experts" -> "model"
  * SP       — "kv_seq" -> "model" for long-context decode
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None)
Rules = dict[str, object]

# Baseline 2D (+pod) rules: FSDP over (pod, data) on the "fsdp" logical axis,
# tensor parallelism over "model".
DEFAULT_RULES: Rules = {
    # data axes
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,  # set to "model" for SP long-context decode
    # param/activation axes
    "embed": None,
    "fsdp": ("pod", "data"),  # ZeRO-3 axis: largest param dim not on "model"
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_group": ("pod", "data"),
    "vocab": "model",
    "layers": None,
    "conv": None,
    "state": None,
    "rnn": "model",
}

_local = threading.local()


def current_rules() -> Rules:
    return getattr(_local, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: Rules):
    prev = getattr(_local, "rules", None)
    _local.rules = rules
    try:
        yield
    finally:
        if prev is None:
            del _local.rules
        else:
            _local.rules = prev


def _active_mesh() -> Mesh | None:
    mesh = jax.sharding.get_abstract_mesh()  # set by `jax.sharding.set_mesh`
    if mesh is None or mesh.empty:
        return None
    return mesh


def _axis_len(mesh, name: str) -> int:
    # works for both Mesh and AbstractMesh
    return dict(zip(mesh.axis_names, mesh.axis_sizes))[name]


def _spec_for(
    logical_axes: tuple[str | None, ...],
    rules: Rules,
    mesh,
    shape: tuple[int, ...] | None = None,
) -> P:
    """Logical axes -> PartitionSpec.  Shape-aware: a mapping whose mesh-axis
    product does not divide the dimension is dropped (e.g. GQA kv_heads=2 on
    a 16-wide model axis stays replicated; FSDP on dim 0 still shards the
    tensor).  Mesh axes are never used twice in one spec."""
    mesh_axes = set(mesh.axis_names)
    out = []
    used: set[str] = set()
    for i, ax in enumerate(logical_axes):
        if ax is None:
            out.append(None)
            continue
        target = rules.get(ax)
        if target is None:
            out.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        picked = [t for t in target if t in mesh_axes and t not in used]
        if shape is not None and picked:
            dim = shape[i]
            # greedily keep the prefix of mesh axes whose product divides dim
            kept = []
            prod = 1
            for t in picked:
                n = _axis_len(mesh, t)
                if dim % (prod * n) == 0:
                    kept.append(t)
                    prod *= n
            picked = kept
        used.update(picked)
        if not picked:
            out.append(None)
        elif len(picked) == 1:
            out.append(picked[0])
        else:
            out.append(tuple(picked))
    return P(*out)


def shard(x, *logical_axes: str | None):
    """Annotate ``x`` with logical axes; no-op outside a mesh context."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = _spec_for(tuple(logical_axes), current_rules(), mesh, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def logical_sharding(
    mesh: Mesh,
    logical_axes: tuple[str | None, ...],
    rules: Rules | None = None,
    shape: tuple[int, ...] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, _spec_for(tuple(logical_axes), rules or current_rules(), mesh, shape))


def shard_params(mesh: Mesh, axes_tree, rules: Rules | None = None, abstract_tree=None):
    """Pytree of logical-axis tuples -> pytree of NamedShardings.

    ``abstract_tree``: matching pytree of arrays/ShapeDtypeStructs enabling
    shape-aware divisibility fallbacks."""
    rules = rules or current_rules()
    is_leaf = lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)
    if abstract_tree is None:
        return jax.tree.map(lambda axes: logical_sharding(mesh, axes, rules), axes_tree, is_leaf=is_leaf)
    flat_axes, tdef = jax.tree.flatten(axes_tree, is_leaf=is_leaf)
    flat_abs = tdef.flatten_up_to(abstract_tree)
    return tdef.unflatten(
        [logical_sharding(mesh, a, rules, tuple(x.shape)) for a, x in zip(flat_axes, flat_abs)]
    )
