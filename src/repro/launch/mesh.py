"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required for the dry-run's
host-platform device-count override to act first.
"""

from __future__ import annotations

import jax


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis explicitly Auto."""
    return jax.make_mesh(shape, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh.

    Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis
    maps to the DCN/ICI-sparse dimension — only gradient/volume
    all-reduces cross it (see launch/sharding.py and DESIGN.md §4).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist right now (tests / elastic restarts)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch: ('pod','data') when pod exists."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
