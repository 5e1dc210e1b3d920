"""Checkpoint manager: atomicity, async, codec, GC, integrity, elastic."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from repro.checkpoint import CheckpointManager


def _tree(key=0):
    k = jax.random.PRNGKey(key)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "w": jax.random.normal(k1, (64, 32), jnp.float32),
        "b": jax.random.normal(k2, (32,), jnp.bfloat16),
        "nested": {"step": jnp.asarray(7, jnp.int32), "m": jax.random.normal(k3, (8, 8))},
    }


def _assert_tree_equal(a, b, exact=True, rtol=0.0):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    for x, y in zip(fa, fb):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol * max(1.0, float(np.abs(x).max())))


def test_roundtrip_raw_exact(tmp_path):
    mgr = CheckpointManager(str(tmp_path), codec_name="raw")
    tree = _tree()
    meta = mgr.save(10, tree, {"note": "hello"})
    assert meta.bytes_written > 0
    restored, extra = mgr.restore(tree)
    _assert_tree_equal(tree, restored, exact=True)
    assert extra == {"note": "hello"}
    # dtypes preserved (incl. bfloat16)
    assert restored["b"].dtype == jnp.bfloat16


def test_roundtrip_int8_bounded_error(tmp_path):
    mgr = CheckpointManager(str(tmp_path), codec_name="int8")
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (512, 64), jnp.float32)}
    mgr.save(1, tree)
    restored, _ = mgr.restore(tree)
    err = np.abs(np.asarray(restored["w"]) - np.asarray(tree["w"])).max()
    scale = np.abs(np.asarray(tree["w"])).max()
    assert err <= scale / 127.0 * 1.01
    # and it actually compresses vs raw
    raw = CheckpointManager(str(tmp_path) + "_raw", codec_name="raw")
    m_raw = raw.save(1, tree)
    m_q = mgr.save(2, tree)
    assert m_q.bytes_written < 0.4 * m_raw.bytes_written


def test_keep_k_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.steps() == [3, 4]


def test_restore_latest_and_specific(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    t1, t2 = _tree(1), _tree(2)
    mgr.save(1, t1)
    mgr.save(2, t2)
    r2, _ = mgr.restore(t1)  # latest
    _assert_tree_equal(t2, r2)
    r1, _ = mgr.restore(t1, step=1)
    _assert_tree_equal(t1, r1)


def test_async_save_is_visible_after_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_io=True)
    tree = _tree()
    mgr.save(5, tree, block=False)
    mgr.wait()
    assert mgr.latest_step() == 5
    restored, _ = mgr.restore(tree)
    _assert_tree_equal(tree, restored)


def test_torn_checkpoint_is_ignored(tmp_path):
    """A directory without a manifest (kill mid-write) must not be listed and
    must be cleaned on the next manager start (paper: out-of-bid mid-ckpt)."""
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(1, tree)
    torn = os.path.join(str(tmp_path), "step_000000002.tmp")
    os.makedirs(torn)
    np.save(os.path.join(torn, "leaf_00000"), np.zeros(4))
    assert mgr.steps() == [1]
    mgr2 = CheckpointManager(str(tmp_path))
    assert not os.path.exists(torn)
    restored, _ = mgr2.restore(tree)
    _assert_tree_equal(tree, restored)


def test_integrity_check_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(1, tree)
    d = os.path.join(str(tmp_path), "step_000000001")
    victim = [f for f in os.listdir(d) if f.startswith("leaf_")][0]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff\xff")
    with pytest.raises(IOError, match="integrity"):
        mgr.restore(tree)


def test_elastic_restore_to_shardings(tmp_path):
    """Restore onto explicit NamedShardings (single-device mesh here; the
    dry-run exercises 512)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(1, tree)
    shardings = jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)
    restored, _ = mgr.restore(tree, shardings=shardings)
    _assert_tree_equal(tree, restored)
    assert all(x.sharding == NamedSharding(mesh, P()) for x in jax.tree.leaves(restored))


def test_structure_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    with pytest.raises(ValueError):
        mgr.restore({"only": jnp.zeros((2,))})
