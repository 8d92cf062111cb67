"""Device meshes.

``make_production_mesh`` is the assignment-mandated mesh: one v5e pod is a
16x16 ("data", "model") grid; the multi-pod variant prepends a "pod" axis
(2 pods = 512 chips).  Defined as functions so importing this module never
touches jax device state (the dry-run sets the fake device count first).

``make_mesh`` is the one place a mesh is built: every axis is
``AxisType.Auto``, so GSPMD propagates shardings through reshapes and
``vmap`` the way the block-cyclic fuse layout and the contributor-dim
training step expect (``jax.make_mesh`` alone defaults to Explicit axes,
which refuse both).

``make_cold_mesh`` is the ColD Fusion training mesh: the data parallelism is
factored into ("contrib", "replica") — each contributor owns a
(replica x model) slab, local steps all-reduce only over "replica"(+"model"),
and the fusion collective is the only traffic crossing "contrib"/"pod".
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cold_mesh(*, contributors: int = 8, replicas: int = 2, model: int = 16,
                   multi_pod: bool = False):
    """ColD mesh: (pod?) x contrib x replica x model.

    contributors*replicas must equal the pod's data extent (16 on the
    production pod) so chip counts match the production mesh.
    """
    if contributors * replicas * model not in (256, jax.device_count(), 512 // (2 if multi_pod else 1)):
        # permissive: tests use small fake meshes
        pass
    shape = (contributors, replicas, model)
    axes = ("contrib", "replica", "model")
    if multi_pod:
        shape = (2,) + shape
        axes = ("pod",) + axes
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """All batch-parallel axes present in a mesh (pod + data/contrib+replica)."""
    names = mesh.axis_names
    out = tuple(a for a in ("pod", "data", "contrib", "replica") if a in names)
    return out
