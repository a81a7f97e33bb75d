"""Production mesh construction (system-prompt contract).

Functions only — importing this module never touches jax device state.

Built on plain `jax.make_mesh(shape, axes)`, axis types left at jax's
default (Explicit in jax 0.9.0).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic re-scale / tests)."""
    return jax.make_mesh(tuple(shape), tuple(axes))


def data_axes_of(mesh) -> tuple:
    """Mesh axes that carry pure data parallelism (pod extends data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_of(mesh) -> str:
    return "model"
