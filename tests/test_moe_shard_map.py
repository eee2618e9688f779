"""shard_map MoE dispatch == GSPMD dispatch on a REAL multi-device mesh.

Needs >1 device, which requires the host-platform override BEFORE jax
initializes — so these run in a subprocess with their own XLA_FLAGS
(the main test process keeps the 1-device contract)."""

import os
import subprocess
import sys

import pytest

SCRIPT = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.distributed.sharding import axis_rules, DEFAULT_RULES

arch = "{arch}"
cfg = get_smoke_config(arch).replace(
    remat=False, compute_dtype="float32", capacity_factor=4.0,
    eval_capacity_factor=4.0)
tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab_size)
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(1))
with jax.set_mesh(mesh), axis_rules(DEFAULT_RULES):
    ref = jax.jit(model.forward)(params, tokens)
cfg2 = cfg.replace(moe_dispatch="shard_map", capacity_factor=8.0,
                   eval_capacity_factor=8.0)
m2 = build_model(cfg2)
with jax.set_mesh(mesh), axis_rules(DEFAULT_RULES):
    out = jax.jit(m2.forward)(params, tokens)
err = float(jnp.abs(ref - out).max())
assert err < 1e-4, err
print("OK", err)
'''


@pytest.mark.parametrize("arch", [
    "grok-1-314b",             # E=4 smoke, not divisible by model=2? E=4 % 2 == 0
    "deepseek-v2-lite-16b",    # E=8 smoke, divisible -> expert-parallel regime
])
def test_shard_map_matches_gspmd_on_4_devices(arch):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT.format(arch=arch)],
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
