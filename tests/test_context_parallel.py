"""Sync-free context-parallel decode (shard_map): correctness vs unsharded
reference AND the collective-count claim (consmax: 1 all-reduce; softmax: >1
+ more bytes) on an 8-virtual-device mesh in a subprocess."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax import random
from repro.core.context_parallel import make_cp_decode
from repro.core import attention as A
from repro.configs.base import ConSmaxConfig
from repro.core.consmax import consmax_init
from repro.nn.module import Ctx
from repro.distributed.hlo_analysis import collective_stats

mesh = jax.make_mesh((8,), ("seq",))
b, L, H, hkv, d = 2, 256, 4, 2, 16
q = random.normal(random.key(1), (b, 1, H, d), jnp.float32) * 0.1
k = random.normal(random.key(2), (b, L, hkv, d), jnp.float32)
v = random.normal(random.key(3), (b, L, hkv, d), jnp.float32)
idx = jnp.array([200, 131], jnp.int32)
params = consmax_init(Ctx(random.key(0)), "n", H, ConSmaxConfig())
out = {}
for kind in ("consmax", "softmax"):
    fn = make_cp_decode(mesh, "seq", kind, params, merged=(kind == "consmax"))
    with jax.set_mesh(mesh):
        res = jax.jit(fn)(q, k, v, idx)
        hlo = jax.jit(fn).lower(q, k, v, idx).compile().as_text()
    ref = A.decode_attention(q, k, v, idx, norm_kind=kind,
                             norm_params=params, merged=(kind == "consmax"))
    rel = float(jnp.max(jnp.abs(res - ref))
                / (jnp.max(jnp.abs(ref)) + 1e-30))
    st = collective_stats(hlo, link_bw=50e9, num_devices=8)
    out[kind] = {"rel_err": rel, "counts": dict(st.count_by_kind),
                 "bytes": st.total_bytes}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_cp_decode_collective_structure():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # virtual CPU devices, never the chip
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.getcwd(),
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["consmax"]["rel_err"] < 1e-5
    assert out["softmax"]["rel_err"] < 1e-5
    n_cs = sum(out["consmax"]["counts"].values())
    n_sm = sum(out["softmax"]["counts"].values())
    assert n_cs == 1, out            # the paper's sync-free property
    assert n_sm > n_cs, out
    assert out["softmax"]["bytes"] > out["consmax"]["bytes"]
