"""Mesh registry + sharding rules.

Models never hold a mesh: they call :func:`shard` with a logical
PartitionSpec, which is a no-op unless a mesh has been activated via
:func:`set_mesh` (dry-run, train, serve do; smoke tests don't).  This keeps
every model runnable on a bare CPU while the launcher gets full control of
placement.

Axis conventions (DESIGN.md):
  * ``DP``   - data-parallel axes: ("pod", "data") on the multi-pod mesh,
               ("data",) on the single-pod mesh.
  * "model"  - tensor/expert-parallel axis.
  * FSDP     - parameter sharding of the d_model dim of large weights over
               the data axes (required for the 1T-param configs).

Jit-*input* shardings must divide evenly (JAX rejects uneven there), so
:func:`shard_if_divisible` drops any axis that does not divide its dim -
the rule set stays total over all 11 architectures.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def manual_axes() -> frozenset:
    """Mesh axes that are manual (shard_map-bound) at this trace point."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty:
        return frozenset()
    manual = jax.sharding.AxisType.Manual
    return frozenset(
        n for n, t in zip(am.axis_names, am.axis_types) if t == manual
    )


def in_manual_region() -> bool:
    """True when tracing inside a shard_map with manual axes - nested manual
    shard_maps over a different axis set are rejected by JAX, so callers
    (row-parallel matmul, a2a MoE) fall back to their GSPMD paths there."""
    return bool(manual_axes())


def dp_axes() -> tuple:
    """The data-parallel axes of the active mesh ('pod' first if present)."""
    mesh = get_mesh()
    if mesh is None:
        return ("data",)
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def shard(x, *spec):
    """Apply a sharding constraint if a mesh is active; identity otherwise.

    Each entry of ``spec`` is an axis name, a tuple of axis names, or None.
    Mesh axes absent from the active mesh are dropped; non-divisible dims are
    left to GSPMD (uneven constraints are legal on intermediates).
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    # axes already manual (inside an enclosing shard_map) can't appear in
    # with_sharding_constraint specs; auto axes still accept constraints
    manual = manual_axes()

    def _filter(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names and a not in manual)
            return kept if kept else None
        return entry if (entry in names and entry not in manual) else None

    pspec = P(*[_filter(e) for e in spec])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in entry]))
    return mesh.shape[entry]


def shard_if_divisible(mesh: Mesh, shape: Sequence[int], *spec) -> NamedSharding:
    """Build a NamedSharding, dropping axes that don't divide their dim.

    Used for jit-boundary (input/param/cache) shardings, which JAX requires
    to divide evenly.  Axis-name entries not present in ``mesh`` are dropped
    too, so one rule covers single- and multi-pod meshes.
    """
    names = set(mesh.axis_names)
    out = []
    for dim, entry in zip(shape, spec):
        if entry is None:
            out.append(None)
            continue
        entries = entry if isinstance(entry, (tuple, list)) else (entry,)
        kept = []
        size = 1
        for a in entries:
            if a not in names:
                continue
            nxt = size * mesh.shape[a]
            if dim % nxt == 0:
                kept.append(a)
                size = nxt
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    # trailing dims beyond spec -> replicated
    while len(out) < len(shape):
        out.append(None)
    return NamedSharding(mesh, P(*out))
