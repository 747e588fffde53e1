"""Shared neural-net layers (pure JAX, no flax): norms, projections, RoPE.

All parameters are plain pytrees of jnp arrays. Initializers take an
explicit key. ``param_dtype`` controls storage, ``compute_dtype`` the
activation math (mixed precision: bf16 compute is the TPU default).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal_init(key, shape, scale, dtype):
    # 1/sqrt(fan_in)-style scaling is applied by callers via `scale`.
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(
        dtype
    )


def dense_init(key, d_in: int, d_out: int, dtype) -> dict:
    w = truncated_normal_init(key, (d_in, d_out), d_in**-0.5, dtype)
    return {"kernel": w}


def dense_init_bias(key, d_in: int, d_out: int, dtype) -> dict:
    p = dense_init(key, d_in, d_out, dtype)
    p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    y = x.astype(compute_dtype) @ params["kernel"].astype(compute_dtype)
    if "bias" in params:
        y = y + params["bias"].astype(compute_dtype)
    return y


def embed_init(key, vocab: int, d_model: int, dtype) -> dict:
    return {
        "table": truncated_normal_init(
            key, (vocab, d_model), d_model**-0.5, dtype
        )
    }


def embed_apply(params: dict, ids: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    return params["table"].astype(compute_dtype)[ids]


def unembed_apply(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """Project to vocab logits with the (possibly tied) embedding table."""
    return jnp.einsum(
        "...d,vd->...v", x.astype(compute_dtype),
        params["table"].astype(compute_dtype),
    )


def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(
    params: dict, x: jnp.ndarray, eps: float, compute_dtype
) -> jnp.ndarray:
    # Normalize in fp32 for stability, multiply in compute dtype.
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(compute_dtype) * params["scale"].astype(
        compute_dtype
    )


def softcap(x: jnp.ndarray, cap: float | None) -> jnp.ndarray:
    """Gemma2-style logit soft-capping: cap·tanh(x/cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # [head_dim/2]


def _rotate_half_matrix(head_dim: int) -> np.ndarray:
    """The signed permutation ``P`` with ``x @ P == [x2, -x1]`` for the
    halves ``x1, x2`` of the last axis: ``P[h+i, i] = +1``,
    ``P[i, h+i] = -1``, ``h = head_dim / 2``."""
    h = head_dim // 2
    p = np.zeros((head_dim, head_dim), np.float32)
    p[np.arange(h) + h, np.arange(h)] = 1.0
    p[np.arange(h), np.arange(h) + h] = -1.0
    return p


def apply_rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float
) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq].

    The half-split rotation ``[x1·cos − x2·sin, x2·cos + x1·sin]``,
    computed on the whole head as ``x·[cos, cos] − (x @ P)·[sin, sin]``
    with ``x @ P = [x2, −x1]`` (``_rotate_half_matrix``). Each element of
    that product is ± one element of ``x``, exact with float32 operands
    at HIGHEST precision (the TPU's default would round ``x`` to
    bfloat16), so the result equals the split form's bit for bit, with
    float32 math and one rounding to ``x.dtype``; so does the gradient
    where each operation rounds on its own. The subtraction keeps
    ``x·cos`` its first operand, as ``x1·cos − x2·sin`` has it, so a
    compiler that fuses the first product into a multiply-add (the
    CPU's) rounds both forms' outputs alike.

    Splitting ``x`` into ``head_dim/2`` halves and concatenating them
    lays each half out with the half as its minor dimension: at head_dim
    64 on qwen1.5-0.5b (16 KV heads, 4 x 2048 tokens) a v5e spent 0.35
    ms a layer on that forward fusion alone, with more of the same in
    recompute and backward.
    """
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..,S,hd/2]
    angles = jnp.concatenate([angles, angles], axis=-1)[..., None, :]
    x32 = x.astype(jnp.float32)
    rot = jnp.matmul(
        x32, jnp.asarray(_rotate_half_matrix(d)),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    out = x32 * jnp.cos(angles) - rot * jnp.sin(angles)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, d_model, d_ff, dtype),
        "up": dense_init(k2, d_model, d_ff, dtype),
        "down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp_apply(params: dict, x: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    gate = jax.nn.silu(dense_apply(params["gate"], x, compute_dtype))
    up = dense_apply(params["up"], x, compute_dtype)
    return dense_apply(params["down"], gate * up, compute_dtype)
