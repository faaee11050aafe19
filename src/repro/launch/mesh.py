"""Production mesh builders. Functions, not module constants — importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 v5e pod slice, or 2 pods = 512 chips with a leading DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_mesh(shape, axes=None):
    """Arbitrary mesh (tests / examples / elastic reconfiguration)."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
    )


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
