"""Recurrent sequence mixers: Mamba (Jamba) and mLSTM/sLSTM (xLSTM).

Each mixer provides:
  * ``apply_train``  — the full-sequence form the model trains with
    (associative scan for Mamba, stabilized quadratic parallel form for
    mLSTM, time scan for sLSTM),
  * ``init_state`` / ``apply_decode`` — O(1)-per-token recurrent stepping,
    the plain reference that tests check each ``apply_train`` against.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — Jamba's sequence mixer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    d_model: int
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model


def mamba_init(key, spec: MambaSpec, dtype) -> dict:
    ks = jax.random.split(key, 6)
    di, ds = spec.d_inner, spec.d_state
    # S4D-real initialization for A (negative reals).
    a = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, 1))
    return {
        "in_proj": layers.dense_init(ks[0], spec.d_model, 2 * di, dtype),
        "conv": layers.truncated_normal_init(
            ks[1], (spec.d_conv, di), spec.d_conv**-0.5, dtype
        ),
        "conv_bias": jnp.zeros((di,), dtype),
        "x_proj": layers.dense_init(ks[2], di, ds * 2 + 1, dtype),
        "dt_bias": jnp.asarray(
            jnp.log(jnp.expm1(jnp.full((di,), 1e-2))), dtype
        ),
        "dt_proj": layers.dense_init(ks[3], 1, di, dtype),
        "a_log": jnp.log(a).astype(jnp.float32),  # keep fp32 (sensitive)
        "d_skip": jnp.ones((di,), dtype),
        "out_proj": layers.dense_init(ks[4], di, spec.d_model, dtype),
    }


def _mamba_gates(params, u, spec: MambaSpec):
    """Shared input-dependent SSM parameters. u: [B, S, d_inner] post-conv."""
    proj = layers.dense_apply(params["x_proj"], u, jnp.float32)
    dt_raw, bmat, cmat = jnp.split(
        proj, [1, 1 + spec.d_state], axis=-1
    )  # [B,S,1], [B,S,ds], [B,S,ds]
    dt = jax.nn.softplus(
        layers.dense_apply(params["dt_proj"], dt_raw, jnp.float32)
        + params["dt_bias"].astype(jnp.float32)
    )  # [B,S,di]
    a = -jnp.exp(params["a_log"])  # [di, ds]
    decay = jnp.exp(dt[..., None] * a)  # [B,S,di,ds]
    drive = dt[..., None] * bmat[..., None, :]  # [B,S,di,ds]
    return decay, drive, cmat


def mamba_apply_train(params, x, spec: MambaSpec, compute_dtype):
    """x: [B, S, D] -> [B, S, D] via associative scan over time."""
    b, s, _ = x.shape
    xz = layers.dense_apply(params["in_proj"], x, compute_dtype)
    u, z = jnp.split(xz, 2, axis=-1)  # [B,S,di] each
    # Depthwise causal conv along time.
    w = params["conv"].astype(compute_dtype)  # [d_conv, di]
    upad = jnp.pad(u, ((0, 0), (spec.d_conv - 1, 0), (0, 0)))
    uc = sum(
        w[i] * jax.lax.dynamic_slice_in_dim(upad, i, s, axis=1)
        for i in range(spec.d_conv)
    ) + params["conv_bias"].astype(compute_dtype)
    uc = jax.nn.silu(uc)

    decay, drive, cmat = _mamba_gates(params, uc, spec)
    bu = drive * uc.astype(jnp.float32)[..., None]  # [B,S,di,ds]

    # h_t = decay_t * h_{t-1} + bu_t  — associative scan over S.
    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, br + ar * bl

    _, h = jax.lax.associative_scan(combine, (decay, bu), axis=1)
    y = jnp.einsum("bsdn,bsn->bsd", h, cmat)
    y = y.astype(compute_dtype) + params["d_skip"].astype(compute_dtype) * uc
    y = y * jax.nn.silu(z)
    return layers.dense_apply(params["out_proj"], y, compute_dtype)


def mamba_init_state(batch: int, spec: MambaSpec, dtype) -> dict:
    return {
        "conv": jnp.zeros((batch, spec.d_conv - 1, spec.d_inner), dtype),
        "ssm": jnp.zeros((batch, spec.d_inner, spec.d_state), jnp.float32),
    }


def mamba_apply_decode(params, x, state, spec: MambaSpec, compute_dtype):
    """Single-step recurrence. x: [B, 1, D]."""
    b = x.shape[0]
    xz = layers.dense_apply(params["in_proj"], x, compute_dtype)
    u, z = jnp.split(xz, 2, axis=-1)  # [B,1,di]
    hist = jnp.concatenate([state["conv"], u], axis=1)  # [B,d_conv,di]
    w = params["conv"].astype(compute_dtype)
    uc = jnp.einsum("bcd,cd->bd", hist, w) + params["conv_bias"].astype(
        compute_dtype
    )
    uc = jax.nn.silu(uc)[:, None, :]  # [B,1,di]

    decay, drive, cmat = _mamba_gates(params, uc, spec)
    h = (
        state["ssm"] * decay[:, 0]
        + drive[:, 0] * uc.astype(jnp.float32)[:, 0, :, None]
    )
    y = jnp.einsum("bdn,bn->bd", h, cmat[:, 0])[:, None, :]
    y = y.astype(compute_dtype) + params["d_skip"].astype(compute_dtype) * uc
    y = y * jax.nn.silu(z)
    out = layers.dense_apply(params["out_proj"], y, compute_dtype)
    return out, {"conv": hist[:, 1:], "ssm": h}


# ---------------------------------------------------------------------------
# mLSTM (xLSTM's matrix-memory block) — parallel + recurrent forms
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLSTMSpec:
    d_model: int
    num_heads: int
    proj_factor: int = 2  # d_inner = proj_factor · d_model (xLSTM block)

    @property
    def d_inner(self) -> int:
        return self.proj_factor * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_inner // self.num_heads


def mlstm_init(key, spec: MLSTMSpec, dtype) -> dict:
    """xLSTM mLSTM block: up-proj (x, z), per-head block-diagonal q/k/v,
    exponential gates, matrix memory, gated down-proj."""
    ks = jax.random.split(key, 7)
    d, di, h, hd = spec.d_model, spec.d_inner, spec.num_heads, spec.head_dim
    blockdiag = lambda k: layers.truncated_normal_init(
        k, (h, hd, hd), hd**-0.5, dtype
    )
    return {
        "up": layers.dense_init(ks[0], d, 2 * di, dtype),
        "wq": blockdiag(ks[1]),
        "wk": blockdiag(ks[2]),
        "wv": blockdiag(ks[3]),
        "wi": layers.dense_init_bias(ks[4], d, spec.num_heads, dtype),
        "wf": layers.dense_init_bias(ks[5], d, spec.num_heads, dtype),
        "down": layers.dense_init(ks[6], di, d, dtype),
    }


def _mlstm_qkv(params, x, spec: MLSTMSpec, compute_dtype):
    """Returns q,k,v in head space plus gates and the z gating stream."""
    b, s, d = x.shape
    h, hd = spec.num_heads, spec.head_dim
    xz = layers.dense_apply(params["up"], x, compute_dtype)
    xin, z = jnp.split(xz, 2, axis=-1)  # [b,s,di] each
    xh = xin.reshape(b, s, h, hd)
    q = jnp.einsum("bshd,hde->bshe", xh, params["wq"].astype(compute_dtype))
    k = jnp.einsum(
        "bshd,hde->bshe", xh, params["wk"].astype(compute_dtype)
    ) * (hd**-0.5)
    v = jnp.einsum("bshd,hde->bshe", xh, params["wv"].astype(compute_dtype))
    igate = layers.dense_apply(params["wi"], x, jnp.float32)  # [b,s,h]
    fgate = layers.dense_apply(params["wf"], x, jnp.float32)
    return q, k, v, igate, fgate, z


def mlstm_apply_train(params, x, spec: MLSTMSpec, compute_dtype):
    """Stabilized parallel (quadratic) form of mLSTM (xLSTM paper, eq. 2x).

    D_ij = exp(logσ(f) cumulative + i_j − m_i); attention-like weighted sum
    with per-row max-stabilizer m and normalizer max(|sum|, exp(-m)).
    """
    b, s, d = x.shape
    q, k, v, igate, fgate, z = _mlstm_qkv(params, x, spec, compute_dtype)
    logf = jax.nn.log_sigmoid(fgate)  # [b,s,h]
    fcum = jnp.cumsum(logf, axis=1)
    # log decay from j -> i (i >= j): fcum_i − fcum_j  (exclusive of f_j? —
    # state at j includes i_j then decays by f_{j+1..i}).
    dmat = fcum[:, :, None, :] - fcum[:, None, :, :]  # [b, i, j, h]
    dmat = dmat + igate[:, None, :, :]  # + i_j
    causal = jnp.tril(jnp.ones((s, s), bool))
    dmat = jnp.where(causal[None, :, :, None], dmat, -jnp.inf)
    m = jnp.max(dmat, axis=2, keepdims=True)  # [b, i, 1, h]
    dexp = jnp.exp(dmat - m)  # stabilized decay weights
    scores = jnp.einsum(
        "bihd,bjhd->bijh", q.astype(jnp.float32), k.astype(jnp.float32)
    )
    wts = scores * dexp
    norm = jnp.maximum(
        jnp.abs(jnp.sum(wts, axis=2)), jnp.exp(-m[:, :, 0, :])
    )  # [b,i,h]
    y = jnp.einsum("bijh,bjhd->bihd", wts, v.astype(jnp.float32))
    y = (y / (norm[..., None] + 1e-6)).astype(compute_dtype)
    y = y.reshape(b, s, spec.d_inner) * jax.nn.silu(z)
    return layers.dense_apply(params["down"], y, compute_dtype)


def mlstm_init_state(batch: int, spec: MLSTMSpec, dtype) -> dict:
    h, hd = spec.num_heads, spec.head_dim
    return {
        "c": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, h, hd), jnp.float32),
        "m": jnp.full((batch, h), -jnp.inf, jnp.float32),
    }


def mlstm_apply_decode(params, x, state, spec: MLSTMSpec, compute_dtype):
    """Recurrent mLSTM step (xLSTM paper eqs. 19-27). x: [B, 1, D]."""
    b, _, d = x.shape
    q, k, v, igate, fgate, z = _mlstm_qkv(params, x, spec, compute_dtype)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [b,h,hd]
    i_t, f_t = igate[:, 0], fgate[:, 0]  # [b,h]
    logf = jax.nn.log_sigmoid(f_t)
    m_new = jnp.maximum(logf + state["m"], i_t)
    fw = jnp.exp(logf + state["m"] - m_new)[..., None]
    iw = jnp.exp(i_t - m_new)[..., None]
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    c = state["c"] * fw[..., None] + iw[..., None] * (
        kf[..., :, None] * vf[..., None, :]
    )
    n = state["n"] * fw + iw * kf
    qf = q.astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", qf, c)
    den = jnp.maximum(
        jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)), jnp.exp(-m_new)
    )
    y = (num / (den[..., None] + 1e-6)).astype(compute_dtype)
    y = y.reshape(b, 1, spec.d_inner) * jax.nn.silu(z)
    out = layers.dense_apply(params["down"], y, compute_dtype)
    return out, {"c": c, "n": n, "m": m_new}


# ---------------------------------------------------------------------------
# sLSTM (xLSTM's scalar-memory block) — inherently sequential
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SLSTMSpec:
    d_model: int
    num_heads: int  # gates are per-head-block diagonal in the full xLSTM;
                    # we use full projections (simpler, strictly more general)


def slstm_init(key, spec: SLSTMSpec, dtype) -> dict:
    ks = jax.random.split(key, 6)
    d = spec.d_model
    return {
        "wz": layers.dense_init_bias(ks[0], d, d, dtype),
        "wi": layers.dense_init_bias(ks[1], d, d, dtype),
        "wf": layers.dense_init_bias(ks[2], d, d, dtype),
        "wo": layers.dense_init_bias(ks[3], d, d, dtype),
        # Recurrent weights.
        "rz": layers.truncated_normal_init(ks[4], (d, d), d**-0.5, dtype),
        "ri": layers.truncated_normal_init(
            jax.random.fold_in(key, 10), (d, d), d**-0.5, dtype
        ),
        "rf": layers.truncated_normal_init(
            jax.random.fold_in(key, 11), (d, d), d**-0.5, dtype
        ),
        "ro": layers.truncated_normal_init(ks[5], (d, d), d**-0.5, dtype),
        "out": layers.dense_init(jax.random.fold_in(key, 12), d, d, dtype),
    }


def slstm_init_state(batch: int, spec: SLSTMSpec, dtype) -> dict:
    d = spec.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, d), -jnp.inf)}


def _slstm_cell(params, x_t, state, compute_dtype):
    """One sLSTM step with exponential gating + stabilizer (xLSTM eqs.)."""
    hprev = state["h"].astype(compute_dtype)
    pre = lambda wk, rk: (
        layers.dense_apply(params[wk], x_t, jnp.float32)
        + (hprev @ params[rk].astype(compute_dtype)).astype(jnp.float32)
    )
    z = jnp.tanh(pre("wz", "rz"))
    itil = pre("wi", "ri")
    ftil = pre("wf", "rf")
    o = jax.nn.sigmoid(pre("wo", "ro"))
    logf = jax.nn.log_sigmoid(ftil)
    m_new = jnp.maximum(logf + state["m"], itil)
    iw = jnp.exp(itil - m_new)
    fw = jnp.exp(logf + state["m"] - m_new)
    c = fw * state["c"] + iw * z
    n = fw * state["n"] + iw
    h = o * (c / jnp.maximum(n, jnp.exp(-m_new) + 1e-6))
    return {"c": c, "n": n, "h": h, "m": m_new}


def slstm_apply_train(params, x, spec: SLSTMSpec, compute_dtype):
    """x: [B, S, D]; lax.scan over time (sLSTM has no parallel form)."""
    b, s, d = x.shape
    state0 = slstm_init_state(b, spec, compute_dtype)

    def step(state, x_t):
        new = _slstm_cell(params, x_t, state, compute_dtype)
        return new, new["h"]

    _, hs = jax.lax.scan(step, state0, jnp.swapaxes(x, 0, 1))
    y = jnp.swapaxes(hs, 0, 1).astype(compute_dtype)
    return layers.dense_apply(params["out"], y, compute_dtype)


def slstm_apply_decode(params, x, state, spec: SLSTMSpec, compute_dtype):
    new = _slstm_cell(params, x[:, 0], state, compute_dtype)
    y = new["h"].astype(compute_dtype)[:, None, :]
    return layers.dense_apply(params["out"], y, compute_dtype), new
