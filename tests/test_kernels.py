"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mixing_combine import mixing_sgd_combine

FLASH_CASES = [
    # b, h, kv, s, d, window, softcap, dtype
    (2, 4, 2, 128, 64, None, None, jnp.float32),
    (1, 8, 4, 256, 64, 64, None, jnp.float32),
    (2, 4, 4, 128, 128, None, 50.0, jnp.float32),
    (1, 2, 1, 256, 32, 128, 30.0, jnp.float32),
    (1, 4, 2, 128, 64, None, None, jnp.bfloat16),
    (1, 4, 4, 128, 256, 96, None, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_oracle(case):
    b, h, kv, s, d, window, cap, dtype = case
    ks = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, h, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d)).astype(dtype)
    out = flash_attention(q, k, v, window=window, softcap=cap,
                          block_q=128, block_k=128, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, window=window, softcap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        rtol=tol, atol=tol,
    )



FLASH_GRAD_CASES = [
    # b, h, kv, s, d, window, softcap (bf16)
    (1, 6, 2, 256, 64, None, None),   # GQA
    (1, 4, 4, 256, 64, None, None),   # MHA
    (1, 4, 2, 256, 64, 96, 30.0),     # window and softcap
]


@pytest.mark.parametrize("case", FLASH_GRAD_CASES)
def test_flash_attention_gradients_match_oracle(case):
    """The kernel's own backward (dq, dk, dv) against ``jax.grad`` of the
    oracle, in bf16, within 2e-2 of each gradient's largest entry."""
    b, h, kv, s, d, window, cap = case
    ks = jax.random.split(jax.random.key(hash(case) % 2**31), 4)
    q, k, v, do = (
        jax.random.normal(kk, shape).astype(jnp.bfloat16)
        for kk, shape in zip(ks, [(b, h, s, d), (b, kv, s, d),
                                  (b, kv, s, d), (b, h, s, d)])
    )

    def grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v, window=window, softcap=cap)
            return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(functools.partial(flash_attention, block_q=128,
                                  block_k=128, interpret=True))
    want = grads(ref.flash_attention_ref)
    for g, w in zip(got, want):
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=0,
                                   atol=2e-2 * np.abs(w).max())


@pytest.mark.parametrize("n,r,block", [(1 << 16, 3, 16384),
                                       (1 << 14, 1, 1 << 14),
                                       (1 << 15, 6, 4096)])
def test_mixing_combine_matches_oracle(n, r, block):
    ks = jax.random.split(jax.random.key(n + r), 4)
    x = jax.random.normal(ks[0], (n,), jnp.float32)
    recv = jax.random.normal(ks[1], (r, n), jnp.float32)
    w = jax.random.uniform(ks[2], (r + 1,))
    mom = jax.random.normal(ks[3], (n,), jnp.float32)
    out = mixing_sgd_combine(x, recv, w, mom, lr=0.1, block_n=block,
                             interpret=True)
    exp = ref.mixing_sgd_combine_ref(x, recv, w, mom, lr=0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("heads, kv, head_dim, window, cap", [
    (14, 2, 64, None, None), (4, 2, 64, 96, 50.0)])
def test_training_attention_off_tpu_is_the_jnp_path(heads, kv, head_dim,
                                                    window, cap,
                                                    monkeypatch):
    """Off a TPU, a shape the kernel fits lowers to the jnp core, bit for
    bit: outputs and gradients equal those of the path that never
    considers the kernel."""
    from repro.launch.mesh import make_test_mesh
    from repro.models import attention as A

    spec = A.AttnSpec(d_model=128, num_heads=heads, num_kv_heads=kv,
                      head_dim=head_dim, window=window, rope_theta=1e4,
                      softcap=cap, qkv_bias=True)
    params = A.init(jax.random.key(1), spec, jnp.bfloat16)
    x = jax.random.normal(jax.random.key(2), (2, 256, 128), jnp.bfloat16)

    def run():
        def loss(p, x):
            y = A.apply_train(p, x, spec, jnp.bfloat16)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(
            params, x)

    with jax.set_mesh(make_test_mesh((1, 1), ("data", "model"))):
        assert A._fits_kernel(256, head_dim)
        got = jax.tree.leaves(run())
        monkeypatch.setattr(A, "_fits_kernel", lambda s, d: False)
        want = jax.tree.leaves(run())
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("seq, head_dim, mesh_shape, fits", [
    (2048, 64, (1, 1), True),
    (2048, 64, None, False),      # no mesh set: devices unknown
    (2000, 64, (1, 1), False),    # not whole 128 blocks
    (2048, 128, (1, 1), False),   # d**-0.5 not a power of two
    (2048, 256, (1, 1), False),   # block rule overflows VMEM there
])
def test_training_attention_takes_the_kernel_only_where_it_fits(
        seq, head_dim, mesh_shape, fits):
    """The kernel is considered only for whole blocks, a measured head
    dim, and a program traced under a mesh of one device."""
    from repro.launch.mesh import make_test_mesh
    from repro.models import attention as A

    if mesh_shape is None:
        assert A._fits_kernel(seq, head_dim) is fits
    else:
        with jax.set_mesh(make_test_mesh(mesh_shape, ("data", "model"))):
            assert A._fits_kernel(seq, head_dim) is fits


def test_flash_kernel_is_built_with_the_library_s_argument_names():
    """The wrapper builds the splash kernel from the library's internals
    by keyword; a renamed or dropped argument fails here, by name."""
    import inspect

    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mi,
    )

    from repro.kernels import flash_attention as F

    F._kernel.cache_clear()
    kernel = F._kernel(256, 2, True, None, None, 128, 128, True)
    run = inspect.signature(sk._splash_attention).parameters
    assert set(kernel.kwargs) <= set(run)
    assert {"fwd_mask_info", "dq_mask_info", "dkv_mask_info"} <= set(
        inspect.signature(sk.SplashAttentionKernel).parameters)
    assert {"mask", "block_shape"} <= set(
        inspect.signature(mi.process_mask).parameters)
    blocks = kernel.kwargs["block_sizes"]
    assert not blocks.use_fused_bwd_kernel
    assert (blocks.block_q_dq, blocks.block_kv_dq) == (128, 128)
    assert kernel.dq_mask_info is kernel.fwd_mask_info
