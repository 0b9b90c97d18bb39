"""The entry points' persistent compilation cache (``launch.compile_cache``).

Each case runs in a child process on the CPU: JAX reads its cache settings
once per process, and a test worker must not start writing a cache.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, jax, jax.numpy as jnp
from repro.launch import compile_cache
where = compile_cache.enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x + 1.0)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"where": where,
                  "config": jax.config.jax_compilation_cache_dir,
                  "default": str(compile_cache.CACHE_DIR)}))
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_cache_dir_is_used_and_reused(tmp_path):
    first = _run(tmp_path)
    assert first["where"] == first["config"] == str(tmp_path)
    entries = sorted(p.name for p in tmp_path.iterdir())
    assert entries, "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    _run(tmp_path)                      # same programs: cache hits, no entry
    assert sorted(p.name for p in tmp_path.iterdir()) == entries


def test_default_cache_dir_is_fixed_inside_checkout():
    out = _run(None)
    assert out["where"] == out["config"] == out["default"] \
        == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
