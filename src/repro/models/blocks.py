"""Per-kind residual blocks for the training forward.

Every kind exposes two functions:
  init(key, cfg, kind)              -> params
  apply_train(params, x, cfg, kind) -> (x, aux_losses)

so the model can scan over heterogeneous groups uniformly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MOE_KINDS, ModelConfig
from repro.models import attention, layers, moe, ssm


def _attn_spec(cfg: ModelConfig, kind: str) -> attention.AttnSpec:
    window = None
    if kind in ("swa", "swa_moe", "local"):
        window = cfg.sliding_window
    return attention.AttnSpec(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        window=window,
        rope_theta=cfg.rope_theta,
        softcap=cfg.attn_logit_softcap,
        qkv_bias=cfg.qkv_bias,
    )


def _mamba_spec(cfg: ModelConfig) -> ssm.MambaSpec:
    return ssm.MambaSpec(
        d_model=cfg.d_model,
        d_state=cfg.ssm_state_dim,
        d_conv=cfg.ssm_conv_dim,
        expand=cfg.ssm_expand,
    )


def _moe_spec(cfg: ModelConfig) -> moe.MoESpec:
    return moe.MoESpec(
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        num_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_token,
        capacity_factor=cfg.capacity_factor,
    )


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype), jnp.dtype(cfg.compute_dtype)


def _is_attn(kind: str) -> bool:
    return kind in ("attn", "attn_moe", "swa", "swa_moe", "local", "global")


def _has_ffn(kind: str) -> bool:
    return kind not in ("mlstm", "slstm")


NO_AUX = {
    "load_balance_loss": jnp.zeros((), jnp.float32),
    "router_z_loss": jnp.zeros((), jnp.float32),
}


def init(key, cfg: ModelConfig, kind: str) -> dict:
    pdt, _ = _dtype(cfg)
    kmix, kffn = jax.random.split(key)
    p: dict = {"norm1": layers.rmsnorm_init(cfg.d_model, pdt)}
    if _is_attn(kind):
        p["mixer"] = attention.init(kmix, _attn_spec(cfg, kind), pdt)
    elif kind in ("mamba", "mamba_moe"):
        p["mixer"] = ssm.mamba_init(kmix, _mamba_spec(cfg), pdt)
    elif kind == "mlstm":
        p["mixer"] = ssm.mlstm_init(
            kmix, ssm.MLSTMSpec(cfg.d_model, cfg.mlstm_heads), pdt
        )
    elif kind == "slstm":
        p["mixer"] = ssm.slstm_init(
            kmix, ssm.SLSTMSpec(cfg.d_model, cfg.mlstm_heads), pdt
        )
    else:
        raise ValueError(kind)
    if _has_ffn(kind):
        p["norm2"] = layers.rmsnorm_init(cfg.d_model, pdt)
        if kind in MOE_KINDS:
            p["ffn"] = moe.init(kffn, _moe_spec(cfg), pdt)
        else:
            p["ffn"] = layers.mlp_init(kffn, cfg.d_model, cfg.d_ff, pdt)
    return p


def _mixer_train(params, x, cfg: ModelConfig, kind: str, cdt):
    if _is_attn(kind):
        return attention.apply_train(params, x, _attn_spec(cfg, kind), cdt)
    if kind in ("mamba", "mamba_moe"):
        return ssm.mamba_apply_train(params, x, _mamba_spec(cfg), cdt)
    if kind == "mlstm":
        return ssm.mlstm_apply_train(
            params, x, ssm.MLSTMSpec(cfg.d_model, cfg.mlstm_heads), cdt
        )
    if kind == "slstm":
        return ssm.slstm_apply_train(
            params, x, ssm.SLSTMSpec(cfg.d_model, cfg.mlstm_heads), cdt
        )
    raise ValueError(kind)


def _mixer_scope(kind: str) -> str:
    """The named scope of a kind's mixer: ``attention`` for every
    attention kind, else the kind's own name (``mamba``, ``mlstm``, …)."""
    if _is_attn(kind):
        return "attention"
    return "mamba" if kind in ("mamba", "mamba_moe") else kind


def apply_train(params, x, cfg: ModelConfig, kind: str):
    """Residual block. The mixer and the FFN run under named scopes
    (``attention``/``mamba``/``mlstm``/``slstm``, ``mlp``/``moe``) that
    ``launch.train.op_scopes`` reads; norms and residual adds stay under
    the caller's."""
    _, cdt = _dtype(cfg)
    h = layers.rmsnorm_apply(params["norm1"], x, cfg.norm_eps, cdt)
    with jax.named_scope(_mixer_scope(kind)):
        y = _mixer_train(params["mixer"], h, cfg, kind, cdt)
    x = x + y
    aux = dict(NO_AUX)
    if _has_ffn(kind):
        h = layers.rmsnorm_apply(params["norm2"], x, cfg.norm_eps, cdt)
        if kind in MOE_KINDS:
            with jax.named_scope("moe"):
                y, aux = moe.apply(params["ffn"], h, _moe_spec(cfg), cdt)
        else:
            with jax.named_scope("mlp"):
                y = layers.mlp_apply(params["ffn"], h, cdt)
        x = x + y
    return x, aux
