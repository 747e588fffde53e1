"""Per-leaf norms and the gaps by which a training run departs from the
reference.

A leaf here is one parameter array, and under ``blocks`` one layer's
slice of it (the layers are stacked on a leading axis), so a fault in a
single layer shows as that layer's gap. The gap of a leaf compares norms
(not the norm of the difference): ``| |x| - |x_ref| |`` over the larger
of ``|x_ref|`` and the median leaf's reference norm, since some leaves'
gradients are all but zero. Where norms cannot tell two precisions
apart, ``worst_share`` reads the norm of the difference on the same
scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# A leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone (a key bias under softmax) and is left
# out of the update comparison.
STILL_LEAF = 1e-3


def _norm(path, x):
    x = x.astype(jnp.float32)
    if jax.tree_util.keystr(path[:1]) == "['blocks']":
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))


def leaf_norms(tree):
    """Tree of per-leaf norms: one per layer under ``blocks``."""
    return jax.tree_util.tree_map_with_path(_norm, tree)


def delta_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


stacked_norms = jax.jit(jax.vmap(leaf_norms))
stacked_delta_norms = jax.jit(jax.vmap(delta_norms))
agent_norms = jax.jit(leaf_norms)
agent_delta_norms = jax.jit(delta_norms)


def flat(tree) -> dict:
    """``{leaf[layer]: norm}`` on the host."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        v = np.asarray(v, dtype=np.float64)
        name = jax.tree_util.keystr(path)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            for i, x in enumerate(v.ravel()):
                out[f"{name}[{i}]"] = float(x)
    return out


def unstack(tree, agents: int) -> list:
    """Per-agent flat dicts from a tree whose leaves lead with the agent
    axis."""
    host = jax.tree.map(np.asarray, tree)
    return [flat(jax.tree.map(lambda x: x[a], host)) for a in range(agents)]


def worst_gap(got: list, want: list, skip=None) -> tuple[float, str]:
    """Largest leaf gap over agents, and where. ``got``/``want`` are
    per-agent ``{leaf: norm}``; leaves in ``skip`` are left out."""
    skip = skip or set()
    keys = [k for k in want[0] if k not in skip]
    median = float(np.median([w[k] for w in want for k in keys]))
    worst, where = 0.0, ""
    for a, (g, w) in enumerate(zip(got, want)):
        for k in keys:
            gap = abs(g.get(k, np.nan) - w[k]) / max(w[k], median)
            if not np.isfinite(gap):
                gap = float("inf")
            if gap > worst:
                worst = gap
                where = f"agent {a} {k}: {g.get(k)!r} vs {w[k]!r}"
    return worst, where


def worst_share(diffs: list, want: list) -> tuple[float, str]:
    """Largest leaf norm of a difference, ``diffs``, over the larger of
    the reference leaf's norm and the median leaf's, and where."""
    median = float(np.median([v for w in want for v in w.values()]))
    worst, where = 0.0, ""
    for a, (d, w) in enumerate(zip(diffs, want)):
        for k, v in d.items():
            share = v / max(w[k], median)
            if not np.isfinite(share):
                share = float("inf")
            if share > worst:
                worst, where = share, f"agent {a} {k}: {v!r} of {w[k]!r}"
    return worst, where


def still_leaves(ref_grads: list) -> set:
    """Leaves whose reference gradient, on any agent, is under
    ``STILL_LEAF`` of the median leaf's."""
    median = float(np.median([v for g in ref_grads for v in g.values()]))
    return {k for g in ref_grads for k, v in g.items()
            if v < STILL_LEAF * median}
