"""Active-mesh context.

Layers that can exploit mesh axes (ring attention over ``seq``, expert
dispatch over ``model``) look the mesh up here instead of threading it
through every ``apply`` signature. ``use_mesh`` is re-entrant and
trace-safe: it only sets a module-level variable read at trace time.
"""

from __future__ import annotations

import contextlib
from typing import Optional

from jax.sharding import Mesh

_ACTIVE_MESH: Optional[Mesh] = None


def current_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def partitioning_mesh() -> Optional[Mesh]:
    """The active mesh if it spans more than one device, else None: the
    case where GSPMD partitions the jitted step, which it cannot do to a
    Mosaic kernel — Pallas-backed layers ask this before taking one."""
    mesh = _ACTIVE_MESH
    return mesh if mesh is not None and mesh.size > 1 else None


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev
