"""The unified decoder model: embed → scan over block groups → LM head.

Pure-functional API:
  init(cfg, key)                          -> params
  forward(cfg, params, inputs)            -> logits [B, S, V]
  loss(cfg, params, batch)                -> (scalar, metrics)

``inputs`` is a dict: {"tokens": [B, S]} for LMs; the VLM backbone adds
{"patch_embeds": [B, P, D]} (precomputed by the stubbed vision frontend),
and the audio backbone consumes EnCodec token ids directly (the codec
itself is the stub).

Layers are scanned in groups of ``len(cfg.block_pattern)`` heterogeneous
blocks (stacked leading G axis), keeping HLO size O(pattern) instead of
O(num_layers) — essential for 512-device dry-run compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks, layers


def init(cfg: ModelConfig, key) -> dict:
    pdt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 4 + len(cfg.block_pattern))
    params: dict = {
        "embed": layers.embed_init(keys[0], cfg.vocab_size, cfg.d_model, pdt),
        "final_norm": layers.rmsnorm_init(cfg.d_model, pdt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embed_init(
            keys[1], cfg.vocab_size, cfg.d_model, pdt
        )
    if cfg.frontend == "vision_patches":
        params["patch_proj"] = layers.dense_init(
            keys[2], cfg.d_model, cfg.d_model, pdt
        )

    # Stacked per-group block params: vmap init over the group axis.
    g = cfg.num_groups
    block_params = {}
    for i, kind in enumerate(cfg.block_pattern):
        ks = jax.random.split(keys[3 + i], g)
        block_params[f"b{i}_{kind}"] = jax.vmap(
            lambda k: blocks.init(k, cfg, kind)
        )(ks)
    params["blocks"] = block_params
    return params


def _embed_inputs(cfg: ModelConfig, params, inputs) -> jnp.ndarray:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = layers.embed_apply(params["embed"], inputs["tokens"], cdt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, cdt)
    if cfg.frontend == "vision_patches":
        patches = layers.dense_apply(
            params["patch_proj"], inputs["patch_embeds"].astype(cdt), cdt
        )
        x = jnp.concatenate([patches, x], axis=1)
    return x


def _scan_groups(cfg: ModelConfig, params, x, remat: bool = True):
    from repro.models.sharding_hints import constrain

    pattern = cfg.block_pattern

    def group_body(x, gp):
        # NOTE on sequence parallelism: constraining the seq dim over the
        # TP axis here was tried and measured worse (a refuted
        # iteration): GSPMD resolves the boundary constraint with extra
        # reshard collectives instead of RS/AG fusion. Boundaries are
        # batch-pinned only.
        x = constrain(x, ("batch", None, None))
        aux_tot = dict(blocks.NO_AUX)
        for i, kind in enumerate(pattern):
            x, aux = blocks.apply_train(gp[f"b{i}_{kind}"], x, cfg, kind)
            aux_tot = {k: aux_tot[k] + aux[k] for k in aux_tot}
        return x, aux_tot

    # NOTE: jax.checkpoint(prevent_cse=False) was tried here and measured
    # worse on collective bytes (a refuted iteration); the default
    # barriers stay.
    body = jax.checkpoint(group_body) if remat else group_body
    x, auxs = jax.lax.scan(body, x, params["blocks"])
    aux = {k: jnp.sum(v) for k, v in auxs.items()}
    return x, aux


def forward(cfg: ModelConfig, params, inputs, remat: bool = True):
    """Training/scoring forward pass → (logits, aux_losses).

    Named scopes ``embed``, ``blocks`` and ``head_loss`` tag the compiled
    ops (HLO metadata only) for ``launch.train.op_scopes``.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("embed"):
        x = _embed_inputs(cfg, params, inputs)
    with jax.named_scope("blocks"):
        x, aux = _scan_groups(cfg, params, x, remat=remat)
    with jax.named_scope("head_loss"):
        x = layers.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps, cdt)
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        logits = layers.unembed_apply(table, x, cdt)
        logits = layers.softcap(
            logits.astype(jnp.float32), cfg.final_logit_softcap
        )
    return logits, aux


def loss(
    cfg: ModelConfig,
    params,
    batch,
    moe_aux_weight: float = 1e-2,
    router_z_weight: float = 1e-3,
    remat: bool = True,
):
    """Next-token cross-entropy. batch: {"tokens": [B, S+1], ...}.

    For the VLM backbone, patch positions are prepended by the model and
    excluded from the loss (labels cover text tokens only).
    """
    tokens = batch["tokens"]
    inputs = dict(batch)
    inputs["tokens"] = tokens[:, :-1]
    labels = tokens[:, 1:]

    logits, aux = forward(cfg, params, inputs, remat=remat)
    if cfg.frontend == "vision_patches":
        # Drop the prepended patch positions from the logits; next-token
        # prediction applies to the text stream only.
        logits = logits[:, inputs["patch_embeds"].shape[1]:, :]

    # Sharded-vocab cross entropy: log_softmax + take_along_axis gathers a
    # replicated [tokens, V] fp32 tensor when V is TP-sharded (measured
    # +26 GB/chip collectives on xlstm). Instead reduce over the
    # vocab dim directly — XLA fuses the mask/exp into the reductions and
    # only [tokens]-sized partials cross shards.
    with jax.named_scope("head_loss"):
        lg = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
        vocab_iota = jnp.arange(lg.shape[-1], dtype=labels.dtype)
        label_logit = jnp.sum(
            jnp.where(vocab_iota[None, None, :] == labels[..., None], lg, 0.0),
            axis=-1,
        )
        nll = lse - label_logit
        ce = jnp.mean(nll)
        total = (
            ce
            + moe_aux_weight * aux["load_balance_loss"]
            + router_z_weight * aux["router_z_loss"]
        )
    metrics = {"ce": ce, **aux}
    return total, metrics


def parameter_count(cfg: ModelConfig, params=None) -> int:
    import math

    if params is None:
        params = jax.eval_shape(lambda k: init(cfg, k), jax.random.key(0))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(params))
