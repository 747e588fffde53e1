"""GQA attention: full / sliding-window / local-global, train + decode.

Training attention (``apply_train``) lowered for a TPU under a mesh of one
device, at a sequence and head size the kernel fits, runs the blocked
Pallas kernel of ``repro.kernels.flash_attention`` with its own backward;
everywhere else (the CPU, a multi-device step or one traced with no mesh
set, other shapes) it runs the jnp core ``_attend``. Prefill and decode
are jnp only.

Cache layout (per layer): {"k": [B, S_cache, H_kv, Dh], "v": same,
"pos": scalar int32 next write position}. Sliding-window layers allocate
S_cache = window and write round-robin; global layers allocate the full
context.
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


def init_cache(
    batch: int, max_len: int, spec: AttnSpec, dtype
) -> dict:
    s_cache = min(max_len, spec.window) if spec.window else max_len
    shape = (batch, s_cache, spec.num_kv_heads, spec.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def apply_decode(
    params, x, cache, spec: AttnSpec, compute_dtype
) -> tuple[jnp.ndarray, dict]:
    """Single-token decode. x: [B, 1, D]; cache as from ``init_cache``.

    Sliding-window layers use the cache as a ring buffer (slot = pos mod
    window); global layers append at pos. Positions are the true token
    positions, so RoPE is correct in both cases.
    """
    b, one, _ = x.shape
    pos = cache["pos"]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, spec, positions, compute_dtype)

    s_cache = cache["k"].shape[1]
    slot = pos % s_cache if spec.window is not None else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)

    # Valid-key mask: ring buffer ⇒ every slot < min(pos+1, S_cache) valid;
    # global ⇒ slots ≤ pos valid.
    idx = jnp.arange(s_cache)[None, :]
    if spec.window is not None:
        valid = idx < jnp.minimum(pos + 1, s_cache)
    else:
        valid = idx <= pos
    mask = jnp.broadcast_to(valid, (b, s_cache))[:, None, :]  # [B,1,Sk]

    out = _sdpa_decode(q, k, v, mask, spec, compute_dtype)
    out = layers.dense_apply(
        params["wo"], out.reshape(b, 1, -1), compute_dtype
    )
    return out, {"k": k, "v": v, "pos": pos + 1}


def _sdpa_decode(q, k, v, mask, spec: AttnSpec, compute_dtype):
    """Decode needs rope on cached K at their *stored* positions; we store
    K post-rope (written in apply_decode/prefill), so plain SDPA applies."""
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


def prefill_cache(
    params, x, spec: AttnSpec, compute_dtype, max_len: int
) -> tuple[jnp.ndarray, dict]:
    """Run full-sequence attention AND build the decode cache. x:[B,S,D]."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, x, spec, positions, compute_dtype)
    out = _attend(q, k, v, spec, compute_dtype, spec.window)
    y = layers.dense_apply(params["wo"], out.reshape(b, s, -1), compute_dtype)

    cache = init_cache(b, max_len, spec, compute_dtype)
    s_cache = cache["k"].shape[1]
    if spec.window is not None and s >= s_cache:
        # Keep the last `window` keys, aligned to ring-buffer slots.
        tail = s - s_cache
        ks, vs = k[:, tail:], v[:, tail:]
        # slot of absolute position p is p % s_cache
        perm = (jnp.arange(s_cache) + tail) % s_cache
        inv = jnp.argsort(perm)
        cache_k = ks[:, inv]
        cache_v = vs[:, inv]
    else:
        pad = s_cache - s
        cache_k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache_v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {
        "k": cache_k.astype(compute_dtype),
        "v": cache_v.astype(compute_dtype),
        "pos": jnp.asarray(s, jnp.int32),
    }
    return y, cache
