"""EP (shard_map) MoE must match the annotation-dispatch MoE numerically."""

import os
import subprocess
import sys

import pytest

# needs >1 device: run the check in a subprocess with fake devices
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import dataclasses
import jax, jax.numpy as jnp, numpy as np

from repro.configs import get_smoke_config
from repro.models import moe as M
from repro.models import moe_ep as MEP
from repro.models import transformer as T

cfg = dataclasses.replace(
    get_smoke_config("kimi-k2-1t-a32b"), n_experts=8, top_k=2, capacity_factor=8.0
)
params = T.init_params(cfg, jax.random.PRNGKey(0))
p0 = params["layers"][0]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model), jnp.float32)

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.sharding.set_mesh(mesh):
    y_ep, aux_ep = jax.jit(lambda p, x: MEP.apply_moe_ep(cfg, p, "moe", x))(p0, x)
    y_dn, aux_dn = jax.jit(lambda p, x: M.apply_moe(cfg, p, "moe", x))(p0, x)
np.testing.assert_allclose(np.asarray(y_ep, np.float32), np.asarray(y_dn, np.float32), atol=2e-5, rtol=2e-5)
np.testing.assert_allclose(float(aux_ep["load_balance_loss"]), float(aux_dn["load_balance_loss"]), rtol=1e-5)
assert float(aux_ep["drop_frac"]) == float(aux_dn["drop_frac"]) == 0.0

# grads must flow through the shard_map path
def loss(p, x):
    y, aux = MEP.apply_moe_ep(cfg, p, "moe", x)
    return jnp.sum(y.astype(jnp.float32) ** 2) + aux["load_balance_loss"]

with jax.sharding.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(p0, x)
for k, v in g.items():
    if k.startswith("moe."):
        assert np.isfinite(np.asarray(v, np.float32)).all(), k
assert float(jnp.max(jnp.abs(g["moe.wi_up"].astype(jnp.float32)))) > 0
print("EP_MOE_OK")
"""


def test_moe_ep_matches_dense_dispatch():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        timeout=600,
    )
    assert "EP_MOE_OK" in r.stdout, r.stdout + r.stderr
