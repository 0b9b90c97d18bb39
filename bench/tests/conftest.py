"""The benchmark's own tests (run explicitly: they are not tier-1's).

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def pytest_configure(config):
    # the tests compile for the CPU at smoke size: keep JAX's persistent
    # cache (which the harness turns on) out of the checkout's directory
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
