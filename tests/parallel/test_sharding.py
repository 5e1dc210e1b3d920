"""Logical-axis sharding rules: mapping, dedup, divisibility fallback."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import DEFAULT_RULES, _spec_for, axis_rules, current_rules, logical_sharding


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 1:
        pytest.skip("no devices")
    # single device, but axis sizes still drive divisibility logic via names
    return jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))


class FakeMesh:
    """Mesh stand-in with arbitrary axis sizes (spec logic is pure)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.axis_sizes = tuple(axes.values())


def test_basic_mapping():
    m = FakeMesh(data=16, model=16)
    spec = _spec_for(("batch", "seq", "embed"), DEFAULT_RULES, m, (256, 4096, 4096))
    assert spec == P("data", None, None)  # "pod" absent on single-pod mesh


def test_multi_pod_batch_uses_both_axes():
    m = FakeMesh(pod=2, data=16, model=16)
    spec = _spec_for(("batch", "seq"), DEFAULT_RULES, m, (256, 4096))
    assert spec == P(("pod", "data"), None)


def test_mesh_axis_never_used_twice():
    m = FakeMesh(data=16, model=16)
    # experts and mlp both map to "model": only the first keeps it
    spec = _spec_for(("experts", "fsdp", "mlp"), DEFAULT_RULES, m, (128, 7168, 4864))
    assert spec == P("model", "data", None)


def test_divisibility_fallback_drops_axis():
    m = FakeMesh(data=16, model=16)
    # kv_heads=2 is not divisible by 16 -> replicated
    spec = _spec_for(("fsdp", "kv_heads", "head_dim"), DEFAULT_RULES, m, (4096, 2, 128))
    assert spec == P("data", None, None)
    # but 32 heads shard fine
    spec = _spec_for(("fsdp", "heads", "head_dim"), DEFAULT_RULES, m, (4096, 32, 128))
    assert spec == P("data", "model", None)


def test_divisibility_keeps_prefix_of_tuple():
    m = FakeMesh(pod=2, data=16, model=16)
    # batch=4: divisible by pod(2) but not pod*data(32) -> keep ("pod",)
    spec = _spec_for(("batch",), DEFAULT_RULES, m, (4,))
    assert spec == P("pod")


def test_rules_context_override():
    assert current_rules() is DEFAULT_RULES
    with axis_rules({**DEFAULT_RULES, "kv_seq": "model"}):
        assert current_rules()["kv_seq"] == "model"
    assert current_rules()["kv_seq"] is None


def test_logical_sharding_on_real_mesh(mesh):
    s = logical_sharding(mesh, ("batch", None), DEFAULT_RULES, (8, 16))
    assert s.spec == P("data", None)
    x = jax.device_put(jnp.zeros((8, 16)), s)
    assert x.sharding.spec == P("data", None)


def test_shard_noop_outside_mesh():
    from repro.parallel import shard

    x = jnp.ones((4, 4))
    assert shard(x, "batch", "embed") is x
