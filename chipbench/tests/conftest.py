"""CPU test set-up for the benchmark's own tests.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests

The CPU backend makes 8 host devices, as ``tests/conftest.py`` has it,
unless ``XLA_FLAGS`` already sets the count: a four-chip cell runs on 4
of them.  The flag must be set before JAX makes its first device.
"""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_DEVICE_FLAG = "xla_force_host_platform_device_count"
if _DEVICE_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + f" --{_DEVICE_FLAG}=8").strip()
REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture(autouse=True, scope="session")
def _compile_cache_outside_the_checkout(tmp_path_factory):
    """CPU programs have no place in the checkout's compile cache."""
    import jax
    path = str(tmp_path_factory.mktemp("jax-cache"))
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    yield
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old
