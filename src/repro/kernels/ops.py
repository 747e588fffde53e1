"""Public jit'd entry points for the Pallas kernels.

On TPU the Pallas path compiles to Mosaic; elsewhere (CPU CI, this
container) ``interpret=True`` executes the kernel body with the same
block decomposition. ``use_pallas(False)`` routes everything to the jnp
reference — the mode used for the dry-run lowering.
"""

from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mixing_combine import mixing_sgd_combine as _mix_pallas

_USE_PALLAS = True


def use_pallas(enabled: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = enabled


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=None, block_k=None):
    if not _USE_PALLAS:
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap
        )
    return _flash_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=_interpret_default(),
    )


def decode_attention(q, k, v, length, *, softcap=None, block_k=512):
    if not _USE_PALLAS:
        return ref.decode_attention_ref(q, k, v, length, softcap=softcap)
    return _decode_pallas(
        q, k, v, length, softcap=softcap, block_k=block_k,
        interpret=_interpret_default(),
    )


def mixing_sgd_combine(x, recv, weights, momentum, *, lr, block_n=65536):
    if not _USE_PALLAS:
        return ref.mixing_sgd_combine_ref(x, recv, weights, momentum, lr=lr)
    return _mix_pallas(
        x, recv, weights, momentum, lr=lr, block_n=block_n,
        interpret=_interpret_default(),
    )
