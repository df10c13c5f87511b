"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state - required by the dry-run protocol.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler), which the sharding rules in ``repro.distributed``
    assume."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_shapes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(preferred_model: int = 1):
    """Mesh over whatever devices exist (tests / single host)."""
    from repro.runtime.elastic import choose_mesh_shape
    n = len(jax.devices())
    shape, names = choose_mesh_shape(n, preferred_model)
    return make_mesh(shape, names)
