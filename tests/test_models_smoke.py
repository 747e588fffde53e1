"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
asserting output shapes + no NaNs, plus decode-vs-forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models import attention, layers
from repro.models import model as M


def _batch(cfg, b=2, s=32, key=0):
    batch = {
        "tokens": jax.random.randint(
            jax.random.key(key), (b, s + 1), 0, cfg.vocab_size
        )
    }
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.key(key + 1), (b, cfg.num_patches, cfg.d_model),
            jnp.float32,
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init(cfg, jax.random.key(0))
    batch = _batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: M.loss(cfg, p, batch), has_aux=True
    )(params)
    assert jnp.isfinite(loss)
    assert np.isfinite(float(metrics["ce"]))
    for g in jax.tree.leaves(grads):
        assert jnp.all(jnp.isfinite(g))
    logits, _ = M.forward(cfg, params, {
        k: (v[:, :-1] if k == "tokens" else v) for k, v in batch.items()
    })
    s_out = batch["tokens"].shape[1] - 1
    if cfg.frontend == "vision_patches":
        s_out += cfg.num_patches
    assert logits.shape == (2, s_out, cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_teacher_forcing(arch):
    """Prefill S tokens then decode; logits must match the full forward
    pass at the same positions (cache correctness per block kind)."""
    cfg = get_config(arch, smoke=True)
    params = M.init(cfg, jax.random.key(1))
    b, s, extra = 2, 24, 4
    toks = jax.random.randint(jax.random.key(2), (b, s + extra), 0,
                              cfg.vocab_size)
    inputs_full = {"tokens": toks}
    if cfg.frontend == "vision_patches":
        pe = jax.random.normal(
            jax.random.key(3), (b, cfg.num_patches, cfg.d_model), jnp.float32
        )
        inputs_full["patch_embeds"] = pe
    ref_logits, _ = M.forward(cfg, params, inputs_full, remat=False)

    prefill_inputs = {"tokens": toks[:, :s]}
    if cfg.frontend == "vision_patches":
        prefill_inputs["patch_embeds"] = pe
    logits_p, caches = M.prefill(cfg, params, prefill_inputs,
                                 max_len=s + extra + cfg.num_patches
                                 if cfg.frontend == "vision_patches"
                                 else s + extra)
    offset = cfg.num_patches if cfg.frontend == "vision_patches" else 0
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0]),
        np.asarray(ref_logits[:, offset + s - 1]),
        rtol=2e-2, atol=2e-2,
    )
    # decode the next `extra` tokens teacher-forced
    for t in range(extra - 1):
        logits_d, caches = M.decode_step(
            cfg, params, caches, toks[:, s + t : s + t + 1]
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0]),
            np.asarray(ref_logits[:, offset + s + t]),
            rtol=3e-2, atol=3e-2,
        )


@pytest.mark.parametrize("arch", ["mixtral-8x7b", "gemma2-2b",
                                  "jamba-1.5-large-398b", "xlstm-125m"])
def test_full_config_shapes_via_eval(arch):
    """Full configs must build abstractly (no allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda k: M.init(cfg, k), jax.random.key(0))
    n = sum(
        int(np.prod(l.shape)) if 0 not in l.shape else 0
        for l in jax.tree.leaves(shapes)
    )
    assert n > 0


def _rope_split_halves(x, positions, theta):
    """RoPE as two ``head_dim/2`` halves, concatenated: the form
    ``layers.apply_rope`` must reproduce bit for bit."""
    freqs = layers.rope_frequencies(x.shape[-1], theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _assert_same_bits(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("positions", ["train", "decode"])
@pytest.mark.parametrize("head_dim", [32, 64, 128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rope_equals_the_split_form(dtype, head_dim, positions):
    """The whole-head rotation equals the split form bit for bit, jitted
    and op by op, and so does its gradient op by op. Jitted, the CPU
    compiler fuses one product of each gradient sum into a multiply-add,
    the split form the sin product in its first half and the cos product
    in its second, so there the two differ by one product's rounding."""
    b, h = 2, 3
    s = 64 if positions == "train" else 1
    start = 0 if positions == "train" else 1000
    pos = jnp.broadcast_to(jnp.arange(start, start + s), (b, s))
    x = (3 * jax.random.normal(jax.random.key(head_dim), (b, s, h, head_dim))
         ).astype(dtype)
    ct = jax.random.normal(jax.random.key(1), x.shape, jnp.float32)

    def grad(rope):
        return jax.grad(
            lambda x: jnp.sum(rope(x, pos, 1e6).astype(jnp.float32) * ct)
        )

    _assert_same_bits(
        jax.jit(layers.apply_rope, static_argnums=2)(x, pos, 1e6),
        jax.jit(_rope_split_halves, static_argnums=2)(x, pos, 1e6),
    )
    with jax.disable_jit():
        _assert_same_bits(layers.apply_rope(x, pos, 1e6),
                          _rope_split_halves(x, pos, 1e6))
        _assert_same_bits(grad(layers.apply_rope)(x),
                          grad(_rope_split_halves)(x))
    got = jax.jit(grad(layers.apply_rope))(x)
    want = jax.jit(grad(_rope_split_halves))(x)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=2 * float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(ct))),
    )


@pytest.mark.parametrize("window", [None, 8])
def test_rope_leaves_prefill_and_decode_caches_unchanged(window, monkeypatch):
    """Prefill's output and cache, then two decode steps' outputs and
    caches, are bit for bit those of the split form."""
    spec = attention.AttnSpec(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=32, window=window,
        rope_theta=1e4, softcap=None, qkv_bias=True,
    )
    params = attention.init(jax.random.key(0), spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 12, spec.d_model))
    steps = jax.random.normal(jax.random.key(2), (2, 2, 1, spec.d_model))

    def run():
        y, cache = jax.jit(
            lambda p, x: attention.prefill_cache(p, x, spec, jnp.bfloat16, 16)
        )(params, x)
        outs = [y, cache["k"], cache["v"]]
        decode = jax.jit(
            lambda p, x, c: attention.apply_decode(p, x, c, spec, jnp.bfloat16)
        )
        for t in range(steps.shape[1]):
            y, cache = decode(params, steps[:, t], cache)
            outs += [y, cache["k"], cache["v"]]
        return outs

    got = run()
    monkeypatch.setattr(layers, "apply_rope", _rope_split_halves)
    want = run()
    for a, b in zip(got, want):
        _assert_same_bits(a, b)
