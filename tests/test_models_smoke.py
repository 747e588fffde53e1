"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
asserting output shapes + no NaNs, plus the forward's causality and the
jnp attention core's chunked and sliding-window forms."""

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


def _assert_causal(arch, p=15):
    """Replace every token after position ``p``: the logits at positions
    up to ``p`` must keep every bit, and a later position must move."""
    cfg = get_config(arch, smoke=True)
    params = M.init(cfg, jax.random.key(1))
    b, s = 2, 24
    toks = jax.random.randint(jax.random.key(2), (b, s), 0, cfg.vocab_size)
    other = jax.random.randint(jax.random.key(3), (b, s), 0, cfg.vocab_size)
    inputs = {"tokens": toks}
    offset = 0
    if cfg.frontend == "vision_patches":
        inputs["patch_embeds"] = jax.random.normal(
            jax.random.key(4), (b, cfg.num_patches, cfg.d_model), jnp.float32
        )
        offset = cfg.num_patches
    later = {**inputs, "tokens": toks.at[:, p + 1:].set(other[:, p + 1:])}
    forward = jax.jit(
        lambda params, inputs: M.forward(cfg, params, inputs, remat=False)[0]
    )
    want, got = forward(params, inputs), forward(params, later)
    cut = offset + p + 1
    _assert_same_bits(got[:, :cut], want[:, :cut])
    assert not np.array_equal(np.asarray(got[:, cut:]),
                              np.asarray(want[:, cut:]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_is_causal(arch):
    """The training forward of every architecture is causal, bit for bit
    (llava's prepended patches included)."""
    _assert_causal(arch)


def test_causality_check_sees_a_leak(monkeypatch):
    """With attention's causal mask opened to every key, the check above
    fails: it can see a leak."""
    monkeypatch.setattr(
        attention, "causal_mask",
        lambda sq, sk, window: jnp.ones((sq, sk), bool),
    )
    with pytest.raises(AssertionError, match="Arrays are not equal"):
        _assert_causal("qwen2-0.5b")


def _attn_spec(window=None, softcap=None):
    """A small GQA layer: 4 query heads on 2 KV heads."""
    return attention.AttnSpec(
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, window=window,
        rope_theta=1e4, softcap=softcap, qkv_bias=True,
    )


@pytest.mark.parametrize("softcap", [None, 30.0])
@pytest.mark.parametrize("window", [None, 8])
def test_chunked_attention_equals_dense(window, softcap, monkeypatch):
    """``_sdpa_chunked`` (the jnp core at S >= 8192: the step on several
    devices or off a TPU) equals the dense ``_sdpa`` over 4 x 4 chunks."""
    monkeypatch.setattr(attention, "CHUNK_Q", 16)
    monkeypatch.setattr(attention, "CHUNK_K", 16)
    spec = _attn_spec(window, softcap)
    b, s = 2, 64
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = 3 * jax.random.normal(kq, (b, s, spec.num_heads, spec.head_dim))
    k = 3 * jax.random.normal(kk, (b, s, spec.num_kv_heads, spec.head_dim))
    v = jax.random.normal(kv, (b, s, spec.num_kv_heads, spec.head_dim))
    mask = jnp.broadcast_to(attention.causal_mask(s, s, window), (b, s, s))
    dense = attention._sdpa(q, k, v, mask, spec, jnp.float32)
    chunked = attention._sdpa_chunked(q, k, v, spec, jnp.float32, window)
    # The online softmax rescales its running sums once per key chunk, so
    # the two differ by float32 rounding: 2.3e-6 at worst (softcap 30) on
    # outputs up to 3.4. 1e-5 leaves that 4x room and is still far below
    # what a wrong mask or a dropped chunk would move.
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("core", ["dense", "chunked"])
def test_sliding_window_ignores_older_keys(core, monkeypatch):
    """With window 8, ``apply_train``'s jnp core at position t does not
    move, bit for bit, when every input before t - 7 changes; changing
    the input at t - 7 moves it."""
    calls = []
    if core == "chunked":
        chunked = attention._sdpa_chunked
        monkeypatch.setattr(attention, "CHUNKED_ATTN_THRESHOLD", 64)
        monkeypatch.setattr(attention, "CHUNK_Q", 16)
        monkeypatch.setattr(attention, "CHUNK_K", 16)
        monkeypatch.setattr(
            attention, "_sdpa_chunked",
            lambda *a: calls.append(a) or chunked(*a),
        )
    spec = _attn_spec(window=8)
    params = attention.init(jax.random.key(0), spec, jnp.float32)
    b, s, t = 2, 64, 50
    x = jax.random.normal(jax.random.key(1), (b, s, spec.d_model))
    noise = jax.random.normal(jax.random.key(2), x.shape)
    attend = jax.jit(
        lambda x: attention.apply_train(params, x, spec, jnp.float32)
    )
    want = attend(x)
    older = attend(x.at[:, : t - 7].add(noise[:, : t - 7]))
    _assert_same_bits(older[:, t], want[:, t])
    inside = attend(x.at[:, t - 7].add(noise[:, t - 7]))
    assert not np.array_equal(np.asarray(inside[:, t]),
                              np.asarray(want[:, t]))
    assert bool(calls) == (core == "chunked")
