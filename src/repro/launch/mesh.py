"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod:
2x16x16 = 512 chips (pod, data, model) — the pod axis is a second
data-parallel dimension with thin inter-pod links, which the gradient
reduction treats hierarchically (see parallel/collectives.py).

Every axis is ``Auto``: the compiler propagates shardings and the model
code places activations with ``with_sharding_constraint``.  (Bare
``jax.make_mesh`` makes ``Explicit`` axes, under which gathers such as
the embedding lookup need their output sharding spelled out.)  Enter a
mesh with ``jax.set_mesh(mesh)`` so the code under it sees it through
``jax.sharding.get_abstract_mesh()``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.analysis.costmodel import MeshSpec


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary Auto-axis mesh (elastic re-scale / serving / tests)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_spec(mesh) -> MeshSpec:
    s = dict(zip(mesh.axis_names, mesh.devices.shape))
    return MeshSpec(data=s.get("data", 1), model=s.get("model", 1),
                    pod=s.get("pod", 1))
