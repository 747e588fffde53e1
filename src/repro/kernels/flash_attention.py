"""Blocked attention for TPU training: causal, sliding-window, softcap, GQA.

A thin wrapper over JAX's Pallas splash-attention kernels
(``jax.experimental.pallas.ops.tpu.splash_attention``): an online-softmax
forward and its own backward (a custom VJP: one kernel for dq, one for dk
and dv), so neither direction ever builds the ``[S, S]`` scores in HBM.

Every head group goes through the MQA kernel: q is grouped
``[B, KV, G, S, D]`` and the kernel, which takes G query heads on one
key/value head, is vmapped over batch and KV head. MHA is the case G = 1.
``d**-0.5`` is folded into q before the kernel; where it is a power of
two (head_dim 16, 64, 256) the fold is exact in any dtype.

Precision: q·k takes the operands in their own dtype (bf16 in training)
with float32 accumulation; softmax statistics are float32; the forward
multiplies float32 p by v upcast to float32; the backward casts p and dS
to the operands' dtype for its products, accumulating in float32. dq,
dk and dv each build up in float32 across all their blocks and are
rounded once. (The library's fused backward would instead write one dq
per key block in q's dtype and sum those rounded partials.)

Blocks, where the caller gives none, follow the sequence: the largest
of 1024, 512, 256 and 128 that divides it (the fastest with this
backward on a v5e at S = 2048 in both benchmark cells, ``PERF.md`` §5).

Targets TPU; validated on the CPU with ``interpret=True`` against
``ref.flash_attention_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask_info as _mask_info,
)

# Block sizes by preference; the last is the kernel's lane width, its
# smallest block.
BLOCKS = (1024, 512, 256, 128)
MIN_BLOCK = BLOCKS[-1]


@functools.lru_cache(maxsize=None)
def _kernel(seq: int, group: int, causal: bool, window: int | None,
            softcap: float | None, block_q: int, block_k: int,
            interpret: bool):
    """The MQA kernel for one shape; its mask processing runs once per
    shape (a fraction of a second at S = 2048), not once per trace."""
    shape = (seq, seq)
    if window is not None:
        head_mask = splash.LocalMask(
            shape, (window - 1, 0 if causal else None), 0
        )
    elif causal:
        head_mask = splash.CausalMask(shape)
    else:
        head_mask = splash.FullMask(shape)
    blocks = splash.BlockSizes(
        block_q=block_q, block_kv=block_k, block_kv_compute=block_k,
        block_q_dkv=block_q, block_kv_dkv=block_k,
        block_kv_dkv_compute=block_k, block_q_dq=block_q,
        block_kv_dq=block_k, use_fused_bwd_kernel=False,
    )
    # ``splash.make_splash_mqa`` would put the mask tables on a device;
    # kept as numpy they are constants of whatever program traces the
    # kernel, on any mesh, described or attached. The dq kernel shares
    # the forward's blocks, so it shares its tables.
    mask = splash.MultiHeadMask([head_mask] * group)
    fwd, mask_fn = _mask_info.process_mask(mask, (block_q, block_k))
    dkv, _ = _mask_info.process_mask_dkv(mask, (block_q, block_k))
    return splash.splash_attention_kernel.SplashAttentionKernel(
        fwd_mask_info=fwd, dq_mask_info=fwd, dkv_mask_info=dkv,
        block_sizes=blocks, is_mqa=True, save_residuals=False,
        mask_value=splash.splash_attention_kernel.DEFAULT_MASK_VALUE,
        attn_logits_soft_cap=softcap, residual_checkpoint_name=None,
        mask_function=mask_fn, interpret=interpret,
    )


def _block_for(seq: int) -> int:
    return next((b for b in BLOCKS if seq % b == 0), seq)


def flash_attention(
    q: jnp.ndarray,   # [B, H, S, D]
    k: jnp.ndarray,   # [B, KV, S, D]
    v: jnp.ndarray,   # [B, KV, S, D]
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, s, d = q.shape
    kv = k.shape[1]
    if h % kv:
        raise ValueError("q heads must be divisible by kv heads")
    block_q = min(block_q or _block_for(s), s)
    block_k = min(block_k or _block_for(s), s)
    if s % block_q or s % block_k or min(block_q, block_k) < MIN_BLOCK:
        raise ValueError(
            f"sequence length {s} must divide into blocks of at least "
            f"{MIN_BLOCK}: got {block_q}, {block_k}"
        )
    group = h // kv
    kernel = _kernel(s, group, causal, window, softcap, block_q, block_k,
                     interpret)
    qg = (q * jnp.asarray(d**-0.5, q.dtype)).reshape(b, kv, group, s, d)
    out = jax.vmap(jax.vmap(kernel))(qg, k, v)
    return out.reshape(b, h, s, d)
