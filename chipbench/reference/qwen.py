"""Plain float32 reference of a Qwen2-family decoder and its D-PSGD step.

Qwen2 (arXiv:2407.10671) and Qwen1.5 share one block: RMSNorm, then
attention with biased q/k/v projections, rotary position embeddings
(half-split rotation, base ``rope_theta``) and grouped key/value heads
(Qwen1.5: as many as query heads), added to the residual; RMSNorm, then
a SwiGLU MLP, added. A final RMSNorm, and the tied embedding table as
the output head. Loss: mean next-token cross-entropy.

Written straight from that description in ``jax.numpy``: every matrix
product at ``Precision.HIGHEST`` in float32, one sequence per call, and
each layer recomputed in the backward (``jax.checkpoint``) so a
full-width model fits one chip. ``precision="fp8"`` is the control: the
same arithmetic with both operands of every product, in the forward
and in the backward, rounded to float8 e4m3 (scaled per tensor to its
largest entry) first.

The optimizer is one agent's D-PSGD step: SGD with momentum, its
parameters held in the configuration's bfloat16 between steps.

Parameters come as a tree keyed as the program keys its own (embedding
``embed/table``, ``final_norm/scale``, and the layers stacked on a
leading axis under ``blocks/<group>``); the reference reads it by those
names and imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8 e4m3


def _fp8(x):
    """Round to float8 e4m3, scaled per tensor to its largest entry."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_operand(x):
    """A product's operand in float8; its gradient passes straight on."""
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity forward; the gradient arriving at a product is float8."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_fp8(g),))


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":  # every product of forward and backward in fp8
        return _fp8_cotangent(jnp.einsum(spec, _fp8_operand(a),
                                         _fp8_operand(b), precision=HIGHEST))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [S, heads, hd]; rotate the two halves of each head."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _layer(cfg: dict, precision: str, x, lp):
    s = x.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    at, ff = lp["mixer"], lp["ffn"]

    y = _rmsnorm(x, lp["norm1"]["scale"], eps)
    q = _mm("sd,de->se", y, at["wq"]["kernel"], precision) + at["wq"]["bias"]
    k = _mm("sd,de->se", y, at["wk"]["kernel"], precision) + at["wk"]["bias"]
    v = _mm("sd,de->se", y, at["wv"]["kernel"], precision) + at["wv"]["bias"]
    q = _rope(q.reshape(s, h, hd), cfg["rope_theta"])
    k = _rope(k.reshape(s, kv, hd), cfg["rope_theta"])
    v = v.reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=1)
    scores = _mm("qhd,khd->hqk", q, k, precision) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, precision).reshape(s, h * hd)
    x = x + _mm("se,ed->sd", o, at["wo"]["kernel"], precision)

    y = _rmsnorm(x, lp["norm2"]["scale"], eps)
    gate = _mm("sd,df->sf", y, ff["gate"]["kernel"], precision)
    up = _mm("sd,df->sf", y, ff["up"]["kernel"], precision)
    x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, ff["down"]["kernel"],
                precision)
    return x


def sequence_loss(cfg: dict, precision: str, params, tokens):
    """Mean next-token cross-entropy of one sequence ``tokens`` [S + 1]."""
    table = params["embed"]["table"]
    (blocks,) = params["blocks"].values()
    x = table[tokens[:-1]]
    body = jax.checkpoint(functools.partial(_layer, cfg, precision))
    x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x, blocks)
    x = _rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    logits = _mm("sd,vd->sv", x, table, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - label)


@functools.lru_cache(maxsize=None)
def _accumulate(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)

    def step(acc, params, tokens, weight):
        loss, g = jax.value_and_grad(
            functools.partial(sequence_loss, cfg, precision))(params, tokens)
        return jax.tree.map(lambda a, b: a + weight * b, acc, g), loss

    return jax.jit(step, donate_argnums=(0,))


def accumulator(cfg: dict, precision: str = "highest"):
    """Jitted ``(acc, params, tokens, weight) -> (acc + weight * grad,
    loss)`` for one sequence ``tokens`` [S + 1]; ``acc`` is donated."""
    return _accumulate(tuple(sorted(cfg.items())), precision)


def _bf16(x):
    """Round float32 to the nearest bfloat16, kept in float32 (a rounding
    the compiler may not elide, as it may a pair of converts)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def sgd_update(params, momentum, grads, lr, mu):
    """SGD with momentum; parameters kept at bfloat16 between steps."""
    m = jax.tree.map(lambda m_, g: mu * m_ + g, momentum, grads)
    p = jax.tree.map(lambda p_, m_: _bf16(p_ - lr * m_), params, m)
    return p, m
