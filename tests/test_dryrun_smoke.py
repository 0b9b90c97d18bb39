"""Dry-run machinery smoke test: run the full lower->compile->roofline path
on an 8-virtual-device mesh with a reduced config, in a subprocess (so the
main pytest process keeps its single real device)."""
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
import jax
from repro.configs.registry import get_config
from repro.configs.base import TrainConfig
from repro.distributed import sharding as SH, hlo_analysis as HA
from repro.train import step as TS

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("granite-3-2b", smoke=True, d_model=128, n_heads=4,
                 n_kv_heads=4, vocab_size=512)
tcfg = TrainConfig(global_batch=8, seq_len=32, remat="full", microbatch=2)
rules = SH.make_rules(mesh, fsdp=True)
_, train_step = TS.make_train_fns(cfg, tcfg)
abs_state = TS.abstract_state(cfg, tcfg)
st_sh = SH.tree_shardings(abs_state, TS.state_axes(cfg, tcfg), mesh, rules)
bspecs, baxes = TS.batch_specs(cfg, 32, 8)
b_sh = SH.tree_shardings(bspecs, baxes, mesh, rules)

def fn(state, batch):
    with SH.activation_sharding(mesh, rules):
        return train_step(state, batch)

with mesh:
    lowered = jax.jit(fn, in_shardings=(st_sh, b_sh)).lower(abs_state, bspecs)
    compiled = lowered.compile()
cost = HA.cost_summary(compiled)
coll = HA.collective_stats(compiled.as_text(), link_bw=50e9, num_devices=8)
mem = HA.memory_summary(compiled)
print(json.dumps({"flops": cost["flops"], "bytes": cost["bytes"],
                  "coll_bytes": coll.total_bytes,
                  "coll_counts": dict(coll.count_by_kind),
                  "temp": mem["temp_bytes"]}))
"""


@pytest.mark.slow
def test_dryrun_8dev_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"          # virtual CPU devices, never the chip
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.getcwd(),
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["bytes"] > 0
    # FSDP + TP must produce collectives (all-gather of params at minimum)
    assert rec["coll_bytes"] > 0, rec
    assert any(k in rec["coll_counts"] for k in ("all-gather", "all-reduce",
                                                 "reduce-scatter"))
