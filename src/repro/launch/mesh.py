"""Mesh construction for the production deployment and tests.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — required because
the dry-run forces 512 host devices while tests/benches run on 1.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types: the train step places its
    state through NamedShardings and lets GSPMD propagate the rest."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(
    shape=(2, 2), axes=("data", "model")
) -> jax.sharding.Mesh:
    """Small mesh over the first ``prod(shape)`` local devices: forced
    host devices in CPU tests, the chips of one host on TPU."""
    return _auto_mesh(shape, axes)


def agent_axes(mesh: jax.sharding.Mesh, layout: str) -> tuple[str, ...]:
    """Mesh axes whose product forms the D-PSGD agent space."""
    has_pod = "pod" in mesh.axis_names
    if layout in ("data", "data_dp"):
        return ("pod", "data") if has_pod else ("data",)
    if layout == "pod":
        return ("pod",) if has_pod else ()
    raise ValueError(f"unknown agent layout {layout!r}")


def num_agents(mesh: jax.sharding.Mesh, layout: str) -> int:
    n = 1
    for a in agent_axes(mesh, layout):
        n *= mesh.shape[a]
    return max(n, 1)
