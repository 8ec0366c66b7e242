"""The benchmark's own tests: its arithmetic, its discovery, and its
check, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


@pytest.fixture(autouse=True)
def _compile_cache(tmp_path_factory, monkeypatch):
    # keep the CPU's compiled programs out of the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


# sizes that are not whole blocks, as the cells' are not
TINY = {
    "netflix-pnmf": {"users": 60, "movies": 45, "rank": 8, "block_size": 8},
}


@pytest.fixture(scope="session")
def bench():
    from lib import spec
    return spec.load_benchmark()


def tiny_config(name: str) -> dict:
    from lib import spec
    return dict(spec.config(name), **TINY[name])
