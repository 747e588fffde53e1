"""Weights and token batches, made on the device from ``--seed``.

Both are the benchmark's, not the program's: the reference is given the
very same arrays, and nothing the program computed.

Weights fill the program's parameter tree (its shapes and dtypes, read
with ``jax.eval_shape``) leaf by leaf by the leaf's role: matrices and
the embedding table from a normal truncated at two deviations, scaled
by the inverse square root of the fan-in (the table: of the width);
biases from a normal of deviation 0.02; norm scales at one. The leading
agent axis is drawn like any other, so agents start apart, as they are
in the middle of a D-PSGD run.

Tokens: each agent draws ranks from a Zipf law over the vocabulary
(``rank = floor(V ** u) - 1``, ``u`` uniform), and maps rank to token
through its own affine permutation, so every agent sees a skewed
distribution whose frequent tokens differ from the other agents'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BIAS_STD = 0.02
RANK_MULT = 7919  # prime, so rank -> token is a permutation of the vocab
AGENT_SHIFT = 104729


def seed_key(seed: int, stream: int):
    """A key from any whole seed (64 bits are kept) and a stream id."""
    seed = int(seed) % 2**64
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def _leaf(key, path: str, shape, dtype):
    if path.endswith("['scale']"):
        return jnp.ones(shape, dtype)
    if path.endswith("['bias']"):
        return (BIAS_STD * jax.random.normal(key, shape)).astype(dtype)
    fan_in = shape[-1] if path.endswith("['table']") else shape[-2]
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (w * fan_in**-0.5).astype(dtype)


def param_maker(shapes, shardings=None):
    """Jitted ``key -> params`` filling the tree of ``shapes``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = [
            _leaf(jax.random.fold_in(key, i), jax.tree_util.keystr(path),
                  s.shape, s.dtype)
            for i, (path, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=shardings)


def token_maker(shape, vocab: int, sharding=None):
    """Jitted ``(key, index) -> int32 tokens`` of ``shape``, whose leading
    axis is the agent."""
    if vocab * RANK_MULT >= 2**32:
        raise ValueError(f"vocabulary {vocab} too large for the rank map")
    agents = shape[0]

    def make(key, index):
        u = jax.random.uniform(jax.random.fold_in(key, index), shape)
        rank = jnp.floor(jnp.exp(u * np.log(vocab))).astype(jnp.uint32) - 1
        rank = jnp.clip(rank, 0, vocab - 1)
        agent = jnp.arange(agents, dtype=jnp.uint32).reshape(
            (agents,) + (1,) * (len(shape) - 1))
        tok = (rank * RANK_MULT + agent * AGENT_SHIFT) % vocab
        return tok.astype(jnp.int32)

    return jax.jit(make, out_shardings=sharding)
