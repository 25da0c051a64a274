"""CPU test set-up for the benchmark's own tests.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
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
