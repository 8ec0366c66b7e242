"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state. Single-pod: 16×16 =
256 chips (data × model). Multi-pod: 2×16×16 = 512 chips with a leading
pure-DP "pod" axis.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.models.module import MeshRules


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # test-only override (used by tests/test_dryrun_small.py to exercise the
    # full dry-run path on a handful of host devices)
    import os
    env = os.environ.get("REPRO_MESH_MULTI" if multi_pod
                         else "REPRO_MESH_SINGLE")
    if env:
        shape = tuple(int(x) for x in env.split(","))
        assert len(shape) == len(axes), (shape, axes)
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """General mesh for tests / elastic replans. Every axis is ``Auto``:
    the sharding layer (``sharding.ctx``) places arrays with
    ``NamedSharding``s and sharding constraints, which ``jax.make_mesh``'s
    default ``Explicit`` axes reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def default_rules(mesh) -> MeshRules:
    """MeshRules filtered to the axes the mesh actually has."""
    names = tuple(mesh.shape.keys())
    return MeshRules(
        fsdp=tuple(a for a in ("data",) if a in names),
        tensor=tuple(a for a in ("model",) if a in names),
        batch=tuple(a for a in ("pod", "data") if a in names),
    )


def mesh_device_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
