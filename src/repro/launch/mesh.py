"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state - the dry-run sets XLA_FLAGS *before* any jax
device initialization and only then calls make_production_mesh().

Meshes are built with Auto axis types: sharding is left to GSPMD and the
explicit ``shard_map`` regions, as every caller here expects.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-mesh)."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
