"""SP (sequence-sharded) decode must match single-device decode exactly."""

import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.parallel.sharding import DEFAULT_RULES, axis_rules

cfg = get_smoke_config("internlm2-20b")
params = T.init_params(cfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, cfg.vocab_size)

# reference: no mesh, plain decode
_, cache = T.prefill(cfg, params, {"tokens": tokens[:, :1]}, max_len=16, q_block=8, kv_block=8)
ref_logits = None
for i in range(1, 9):
    ref_logits, cache = T.decode_step(cfg, params, tokens[:, i:i+1], cache)

# SP: mesh (2 data, 4 model), kv_seq -> model, cache len 16 % 4 == 0
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = {**DEFAULT_RULES, "kv_seq": "model"}
with jax.sharding.set_mesh(mesh), axis_rules(rules):
    _, cache = T.prefill(cfg, params, {"tokens": tokens[:, :1]}, max_len=16, q_block=8, kv_block=8)
    step = jax.jit(lambda p, t, c: T.decode_step(cfg, p, t, c))
    sp_logits = None
    for i in range(1, 9):
        sp_logits, cache = step(params, tokens[:, i:i+1], cache)

np.testing.assert_allclose(
    np.asarray(sp_logits, np.float32), np.asarray(ref_logits, np.float32), atol=3e-2, rtol=3e-2
)
print("SP_DECODE_OK")
"""


def test_sp_decode_matches_plain():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env, cwd=root, timeout=600
    )
    assert "SP_DECODE_OK" in r.stdout, r.stdout + r.stderr[-3000:]
