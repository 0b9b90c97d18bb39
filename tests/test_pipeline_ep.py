"""Pipeline parallelism (GPipe/ppermute) and expert-parallel all-to-all MoE:
correctness vs sequential/automatic references on 8 virtual devices."""
import json
import os
import subprocess
import sys

import pytest

PIPE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax import random
from repro.distributed.pipeline import gpipe

mesh = jax.make_mesh((4,), ("stage",))
S, M, b, d = 4, 6, 2, 16
ws = random.normal(random.key(0), (S, d, d)) / d**0.5
xs = random.normal(random.key(1), (M, b, d))

def stage_fn(w, x):
    return jnp.tanh(x @ w)

with jax.set_mesh(mesh):
    out = jax.jit(lambda ws, xs: gpipe(stage_fn, ws, xs, mesh=mesh))(ws, xs)

ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ ws[s])
err = float(jnp.max(jnp.abs(out - ref)))
print(json.dumps({"err": err}))
"""

EP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax import random
from repro.configs.registry import get_config
from repro.configs.base import MoEConfig
from repro.models import moe as MOE, moe_ep as MOE_EP
from repro.nn.module import Ctx

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = get_config("phi3.5-moe-42b-a6.6b", smoke=True).replace(
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=256, capacity_factor=8.0))
p = MOE.moe_init(Ctx(random.key(0)), "moe", cfg)
x = random.normal(random.key(1), (8, 16, cfg.d_model)).astype(jnp.bfloat16)
y_ref, _ = MOE.moe_apply(p, x, cfg)
with jax.set_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: MOE_EP.moe_apply_ep(p, x, cfg, mesh,
                                                       "data"))(p, x)
err = float(jnp.max(jnp.abs((y_ep - y_ref).astype(jnp.float32))))
print(json.dumps({"err": err}))
"""


def _run(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # virtual CPU devices, never the chip
    r = subprocess.run([sys.executable, "-c", script], cwd=os.getcwd(),
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_gpipe_matches_sequential():
    assert _run(PIPE)["err"] < 1e-5


@pytest.mark.slow
def test_expert_parallel_matches_auto_path():
    # generous capacity -> no drops -> bit-comparable outputs
    assert _run(EP)["err"] == 0.0
