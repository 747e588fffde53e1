"""Opt-in activation sharding hints for mesh-agnostic model code.

The launch layer knows the mesh ("data"/"model"/"pod" axes); the model
only knows logical roles ("batch", "seq", "tp"). ``set_hints`` installs a
role→axes map; ``constrain`` then pins named dims with
``with_sharding_constraint``. With no hints installed (unit tests, single
device) it is a no-op, so model code can call it unconditionally.

Measured motivation: GSPMD replicated the vmapped MoE dispatch buffers
([B, E·C, D] ≈ 43 GB/chip) in a lowering over 32k-token sequences;
pinning the batch dim restores batch sharding.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec as P

_HINTS: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "sharding_hints", default=None
)


@contextlib.contextmanager
def hints(role_axes: dict):
    """role_axes, e.g. {"batch": ("data",), "tp": ("model",)}."""
    token = _HINTS.set(dict(role_axes))
    try:
        yield
    finally:
        _HINTS.reset(token)


def constrain(x, roles: tuple):
    """roles: per-dim role name or None, e.g. ("batch", "seq", None).

    Divisibility-guarded: a role is dropped if the dim does not divide
    the axes' size (never rely on GSPMD padding)."""
    mapping = _HINTS.get()
    if mapping is None:
        return x
    from repro.launch import mesh as _  # noqa: F401 (no-op, doc link)

    mesh = None
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        pass
    spec = []
    for dim, r in enumerate(roles):
        axes = mapping.get(r) if r else None
        if axes and mesh is not None:
            size = 1
            for a in axes:
                size *= dict(zip(mesh.axis_names, mesh.axis_sizes)).get(a, 1)
            if size <= 1 or x.shape[dim] % size or x.shape[dim] < size:
                axes = None
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(tuple(axes))
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:
        return x  # no mesh context: best-effort no-op
