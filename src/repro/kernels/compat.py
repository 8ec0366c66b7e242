"""Capability probe for the optional Pallas Triton (GPU) lowering.

The kernels target the installed JAX (0.9.0): they import
``jax.experimental.pallas`` and ``pltpu.CompilerParams`` directly, and the
training substrate calls ``jax.shard_map`` directly. The one thing that
still varies by installation is whether the jaxlib was built with the
Triton lowering, which the ``pallas-gpu`` backend tier needs.
"""
from __future__ import annotations

try:  # The Triton lowering ships only in GPU-enabled jaxlibs; resolving it
    # here (and nowhere else) is what lets the ``pallas-gpu`` backend tier
    # register everywhere and capability-gate cleanly on CPU/TPU machines.
    from jax.experimental.pallas import triton as pltriton  # noqa: F401
    _HAS_TRITON = True
except ImportError:
    pltriton = None
    _HAS_TRITON = False


def has_triton() -> bool:
    """True when the Pallas Triton (GPU) lowering imports here. Import
    success alone does not make the backend *runnable* — the registry
    additionally requires the default JAX backend to be a GPU."""
    return _HAS_TRITON
