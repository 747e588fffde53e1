"""Sharding rules: PartitionSpecs for training params and batches.

Roles (resolved to mesh axes per layout):
  agent — stacked D-PSGD agent dim (dim 0 of every train leaf)
  fsdp  — intra-agent parameter/optimizer sharding ("pod" layout only)
  tp    — tensor parallelism over the "model" axis
  ep    — expert parallelism (MoE expert dim)

Train layouts (TrainConfig.agent_layout):
  "data": agents on ("pod"×)"data"; each agent's params live on its data
          rank, TP over "model". Small/mid archs (≤ ~50B).
  "pod" : one agent per pod; FSDP over "data" + TP over "model" inside
          the agent. Big archs (mixtral-8x22b, mistral-large, jamba).

The rules are path-pattern driven and *divisibility-safe*: an axis is
only assigned if the dim divides evenly, else dropped (GSPMD padding is
never relied upon).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


# (pattern, per-dim roles from the END of the shape). Earlier entries win.
# Dims not covered (leading stacked dims G) get None; dim 0 agent handled
# separately. Roles per dim: tuple of candidate roles tried in order.
_PARAM_RULES: tuple[tuple[str, tuple[tuple[str, ...], ...]], ...] = (
    # xLSTM mixer projections: REPLICATED. TP-sharding them was measured
    # forcing ~300 MB activation all-reduces per layer per microbatch
    # (the 4 mLSTM heads cannot align with a 16-way model axis); the
    # model is ≤125M params, so replication is free.
    (r"mixer/(up|down)/kernel$", ((), ())),
    # MoE stacked experts [*, E, D, F] / [*, E, F, D]
    (r"ffn/(gate|up)$", (("ep",), ("fsdp",), ("tp",))),
    (r"ffn/down$", (("ep",), ("tp",), ("fsdp",))),
    (r"router/kernel$", (("fsdp",), ())),
    # Attention / MLP projections
    (r"(wq|wk|wv)/kernel$", (("fsdp",), ("tp",))),
    (r"(wq|wk|wv)/bias$", (("tp",),)),
    (r"wo/kernel$", (("tp",), ("fsdp",))),
    (r"(gate|up)/kernel$", (("fsdp",), ("tp",))),
    (r"down/kernel$", (("tp",), ("fsdp",))),
    # Embeddings
    (r"(embed|unembed)/table$", (("tp",), ("fsdp",))),
    (r"patch_proj/kernel$", (("fsdp",), ("tp",))),
    # Mamba
    (r"in_proj/kernel$", (("fsdp",), ("tp",))),
    (r"out_proj/kernel$", (("tp",), ("fsdp",))),
    (r"mixer/conv$", ((), ("tp",))),
    (r"conv_bias$", (("tp",),)),
    (r"x_proj/kernel$", (("tp",), ())),
    (r"dt_proj/kernel$", ((), ("tp",))),
    (r"(dt_bias|d_skip)$", (("tp",),)),
    (r"a_log$", (("tp",), ())),
    # xLSTM: up/down projected; per-head block-diag weights replicated
    (r"mixer/up/kernel$", (("fsdp",), ("tp",))),
    (r"mixer/down/kernel$", (("tp",), ("fsdp",))),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _assign(shape, roles_from_end, role_axes, mesh) -> P:
    """Build a spec assigning roles to trailing dims, divisibility-safe.

    Each mesh axis is used at most once per leaf.
    """
    spec: list = [None] * len(shape)
    used: set[str] = set()
    n = len(roles_from_end)
    for i, roles in enumerate(roles_from_end):
        dim = len(shape) - n + i
        if dim < 0:
            continue
        for role in roles:
            axes = role_axes.get(role, ())
            axes = tuple(a for a in axes if a not in used)
            if not axes:
                continue
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if shape[dim] % size == 0 and shape[dim] >= size:
                spec[dim] = axes if len(axes) > 1 else axes[0]
                used.update(axes)
                break
    return P(*spec)


def _role_axes_train(mesh, layout: str) -> dict:
    has_pod = "pod" in mesh.axis_names
    if layout == "data":
        return {
            "agent": (("pod", "data") if has_pod else ("data",)),
            "fsdp": (),
            "tp": ("model",),
            "ep": (),
            "batch_inner": (),
        }
    if layout == "data_dp":
        # Small models: replicate weights over "model" and use it as
        # intra-agent data parallelism — kills the per-layer TP
        # all-reduces that dominate sub-1B-model training.
        return {
            "agent": (("pod", "data") if has_pod else ("data",)),
            "fsdp": (),
            "tp": (),
            "ep": (),
            "batch_inner": ("model",),
        }
    if layout == "pod":
        return {
            "agent": (("pod",) if has_pod else ()),
            "fsdp": ("data",),
            "tp": ("model",),
            "ep": ("data",),  # EP and FSDP share the data axis (either/or)
            "batch_inner": ("data",),
        }
    raise ValueError(layout)


def param_specs_train(
    params_shape: Any, mesh, layout: str
) -> Any:
    """Specs for stacked-agent train params (leaf dim 0 = agent)."""
    role_axes = _role_axes_train(mesh, layout)
    agent = role_axes["agent"]

    def spec_for(path, leaf):
        s = _path_str(path)
        shape = leaf.shape
        inner = shape[1:]  # strip agent dim
        rules = None
        for pat, roles in _PARAM_RULES:
            if re.search(pat, s):
                rules = roles
                break
        if rules is None:
            inner_spec = P(*([None] * len(inner)))
        else:
            inner_spec = _assign(inner, rules, role_axes, mesh)
        a0 = None
        if agent:
            size = int(np.prod([mesh.shape[a] for a in agent]))
            if shape[0] % size == 0:
                a0 = agent if len(agent) > 1 else agent[0]
        return P(a0, *inner_spec)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def batch_specs_train(batch_shape: Any, mesh, layout: str) -> Any:
    """Batch leaves are [A, per_agent_B, ...]: agent dim + inner-batch
    sharding per layout (fsdp for "pod", "model" for "data_dp")."""
    role_axes = _role_axes_train(mesh, layout)
    agent, fsdp = role_axes["agent"], role_axes["batch_inner"]

    def spec_for(path, leaf):
        shape = leaf.shape
        a0 = None
        if agent:
            size = int(np.prod([mesh.shape[a] for a in agent]))
            if shape[0] % size == 0:
                a0 = agent if len(agent) > 1 else agent[0]
        b1 = None
        if fsdp and len(shape) > 1:
            size = int(np.prod([mesh.shape[a] for a in fsdp]))
            if shape[1] % size == 0:
                b1 = fsdp if len(fsdp) > 1 else fsdp[0]
        return P(a0, b1, *([None] * (len(shape) - 2)))

    return jax.tree_util.tree_map_with_path(spec_for, batch_shape)
