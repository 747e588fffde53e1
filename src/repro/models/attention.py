"""GQA training attention: full / sliding-window / local-global.

``apply_train`` lowered for a TPU under a mesh of one device, at a
sequence and head size the kernel fits, runs the blocked Pallas kernel
of ``repro.kernels.flash_attention`` with its own backward; everywhere
else (the CPU, a multi-device step or one traced with no mesh set, other
shapes) it runs the jnp core ``_attend``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as flash
from repro.models import layers

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None         # None = full causal
    rope_theta: float
    softcap: float | None      # attention-logit softcap (gemma2)
    qkv_bias: bool


def init(key, spec: AttnSpec, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    mk = layers.dense_init_bias if spec.qkv_bias else layers.dense_init
    return {
        "wq": mk(kq, spec.d_model, spec.num_heads * spec.head_dim, dtype),
        "wk": mk(kk, spec.d_model, spec.num_kv_heads * spec.head_dim, dtype),
        "wv": mk(kv, spec.d_model, spec.num_kv_heads * spec.head_dim, dtype),
        "wo": layers.dense_init(
            ko, spec.num_heads * spec.head_dim, spec.d_model, dtype
        ),
    }


def _project_qkv(params, x, spec: AttnSpec, positions, compute_dtype):
    b, s, _ = x.shape
    q = layers.dense_apply(params["wq"], x, compute_dtype).reshape(
        b, s, spec.num_heads, spec.head_dim
    )
    k = layers.dense_apply(params["wk"], x, compute_dtype).reshape(
        b, s, spec.num_kv_heads, spec.head_dim
    )
    v = layers.dense_apply(params["wv"], x, compute_dtype).reshape(
        b, s, spec.num_kv_heads, spec.head_dim
    )
    if spec.rope_theta > 0:  # theta == 0 ⇒ NoPE (e.g. Jamba attention)
        q = layers.apply_rope(q, positions, spec.rope_theta)
        k = layers.apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, spec: AttnSpec, compute_dtype):
    """Grouped scaled-dot-product attention. q:[B,Sq,H,D] k/v:[B,Sk,Hkv,D]."""
    groups = spec.num_heads // spec.num_kv_heads
    b, sq, h, d = q.shape
    qg = q.reshape(b, sq, spec.num_kv_heads, groups, d)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (d**-0.5)
    logits = layers.softcap(logits, spec.softcap)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(compute_dtype), v)
    return out.reshape(b, sq, h, d)


def causal_mask(sq: int, sk: int, window: int | None) -> jnp.ndarray:
    """[sq, sk] boolean; True = attend. Optionally sliding-window limited."""
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


# Sequences at or above this length use the chunked (flash-style) path:
# the monolithic [Sq, Sk] logits tensor would not fit HBM.
CHUNKED_ATTN_THRESHOLD = 8192
CHUNK_Q = 1024
CHUNK_K = 1024


def _sdpa_chunked(q, k, v, spec: AttnSpec, compute_dtype, window):
    """Online-softmax attention in pure jnp: scan over k chunks inside a
    scan over q chunks. Never materializes more than [B, H, CQ, CK]
    logits — the jnp analogue of the Pallas flash kernel (same math)."""
    b, s, h, d = q.shape
    kv = spec.num_kv_heads
    groups = h // kv
    cq, ck = min(CHUNK_Q, s), min(CHUNK_K, s)
    nq, nk = s // cq, s // ck
    qg = q.reshape(b, nq, cq, kv, groups, d).astype(jnp.float32)
    kg = k.reshape(b, nk, ck, kv, d).astype(jnp.float32)
    vg = v.reshape(b, nk, ck, kv, d).astype(jnp.float32)

    def q_block(iq, q_blk):
        # q_blk: [b, cq, kv, groups, d]
        def k_step(carry, ik_blk):
            m_prev, l_prev, acc = carry
            ik, k_blk, v_blk = ik_blk
            logits = jnp.einsum(
                "bqkgd,bskd->bkgqs", q_blk, k_blk
            ) * (d**-0.5)
            logits = layers.softcap(logits, spec.softcap)
            qpos = iq * cq + jnp.arange(cq)
            kpos = ik * ck + jnp.arange(ck)
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, v_blk
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kv, groups, cq), -jnp.inf)
        l0 = jnp.zeros((b, kv, groups, cq))
        a0 = jnp.zeros((b, kv, groups, cq, d))
        (m_f, l_f, acc), _ = jax.lax.scan(
            k_step,
            (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0)),
        )
        out = acc / jnp.maximum(l_f[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1)  # [b, cq, kv, groups, d]

    out = jax.lax.map(
        lambda args: q_block(*args),
        (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)),
    )  # [nq, b, cq, kv, groups, d]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)
    return out.astype(compute_dtype)


def _attend(q, k, v, spec: AttnSpec, compute_dtype, window):
    """The jnp attention core: ``_sdpa``, or ``_sdpa_chunked`` for long
    sequences. q: [B,S,H,D]; k/v: [B,S,Hkv,D]."""
    b, s = q.shape[:2]
    if s >= CHUNKED_ATTN_THRESHOLD and s % CHUNK_Q == 0 and s % CHUNK_K == 0:
        return _sdpa_chunked(q, k, v, spec, compute_dtype, window)
    mask = jnp.broadcast_to(causal_mask(s, s, window), (b, s, s))
    return _sdpa(q, k, v, mask, spec, compute_dtype)


# Head dims the blocked kernel takes on the training path: d**-0.5 is a
# power of two, so folding it into bf16 q is exact, and the block rule
# was measured there. (At head_dim 256 a 1024 block overflows the dk/dv
# kernel's VMEM on a v5e.)
KERNEL_HEAD_DIMS = (64,)


def _fits_kernel(s: int, head_dim: int) -> bool:
    """Whole kernel blocks, a head dim of ``KERNEL_HEAD_DIMS``, and a
    program traced under a mesh of exactly one device: GSPMD cannot
    partition a Pallas kernel, and with no mesh set (size 0) the step
    may still be jitted over several devices."""
    return (s % flash.MIN_BLOCK == 0 and head_dim in KERNEL_HEAD_DIMS
            and jax.sharding.get_abstract_mesh().size == 1)


def _attend_flash(q, k, v, spec: AttnSpec, window):
    """The blocked Pallas kernel (TPU only), in ``_attend``'s layout."""
    out = flash.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window,
        softcap=spec.softcap,
    )
    return out.transpose(0, 2, 1, 3)


def apply_train(
    params, x, spec: AttnSpec, compute_dtype, window_override=None
) -> jnp.ndarray:
    """Full-sequence training attention. x: [B, S, D].

    Lowered for a TPU, a sequence and head size the kernel fits take the
    blocked Pallas kernel, with its own backward; every other platform
    and shape takes ``_attend``, the jnp core."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, x, spec, positions, compute_dtype)
    window = spec.window if window_override is None else window_override
    if _fits_kernel(s, spec.head_dim):
        out = jax.lax.platform_dependent(
            q, k, v,
            tpu=lambda q, k, v: _attend_flash(q, k, v, spec, window),
            default=lambda q, k, v: _attend(
                q, k, v, spec, compute_dtype, window
            ),
        )
    else:
        out = _attend(q, k, v, spec, compute_dtype, window)
    return layers.dense_apply(
        params["wo"], out.reshape(b, s, -1), compute_dtype
    )
