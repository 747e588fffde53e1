"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(
    q, k, v, *, causal=True, window=None, softcap=None
):
    """q: [B,H,Sq,D]; k/v: [B,KV,Sk,D] → [B,H,Sq,D] (fp32 math)."""
    b, h, sq, d = q.shape
    kv = k.shape[1]
    group = h // kv
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)
    ) * (d**-0.5)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    sk = kk.shape[2]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def mixing_sgd_combine_ref(x, recv, weights, momentum, *, lr):
    acc = x.astype(jnp.float32) * weights[0]
    acc += jnp.einsum(
        "r,rn->n", weights[1:].astype(jnp.float32),
        recv.astype(jnp.float32),
    )
    acc -= lr * momentum.astype(jnp.float32)
    return acc.astype(x.dtype)
