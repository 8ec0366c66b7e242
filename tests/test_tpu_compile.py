"""Compile every kernel that keeps a ``pallas-tpu`` registration for a
described (not attached) TPU v5e chip, at a real width, through the TPU
impl the registry dispatches to. Nothing runs; the chip's compiler only
has to accept each kernel, as it must before any chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, registry

N = 4096
BS = 256
K = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _add(x, y):
    return x + y


def _cases():
    g = N // BS

    def mat(shape, dtype=jnp.float32):
        return (shape, dtype)

    return {
        "masked_matmul": (
            lambda w, h, m: ops._masked_matmul_tpu(w, h, m, block_size=BS),
            [mat((N, K)), mat((K, N)), mat((g, g), bool)]),
        "merge_join": (
            lambda a, b, ma, mb: ops._merge_join_tpu(
                a, b, ma, mb, merge=_add, mode=0, block_size=BS),
            [mat((N, N)), mat((N, N)), mat((g, g), bool),
             mat((g, g), bool)]),
        **{f"sddmm_agg_{dim}": (
            lambda sp, w, h, m, dim=dim: ops._sddmm_agg_tpu(
                sp, w, h, m, dim=dim, block_size=BS),
            [mat((N, N)), mat((N, K)), mat((K, N)), mat((g, g), bool)])
           for dim in ("row", "col", "all")},
    }


CASES = ("masked_matmul", "merge_join", "sddmm_agg_row", "sddmm_agg_col",
         "sddmm_agg_all")


def test_cases_cover_every_tpu_registration():
    # built-in kernels only: other tests register "_"-named scratch ones
    tpu = {name for name in registry.kernels() if not name.startswith("_")
           and registry.TPU in registry.get(name).impls}
    assert tpu == {c.rsplit("_", 1)[0] if c.startswith("sddmm") else c
                   for c in CASES}


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = _cases()[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
