"""Production meshes. v5e pod = 16×16 = 256 chips; multi-pod adds the 'pod'
axis (DCN-connected). Functions, not module constants — importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], names: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None
              ) -> jax.sharding.Mesh:
    """A mesh whose axes are all ``Auto``.

    ``jax.make_mesh`` builds ``Explicit`` axes by default, on which a plain
    gather of a row-sharded array (``ratings[idx]``) has no resolvable output
    sharding. Every mesh of this repo leaves that choice to the partitioner.
    """
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Small mesh for fast iteration (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Single-device mesh (CPU smoke tests): every axis size 1."""
    return make_mesh((1, 1), ("data", "model"))
