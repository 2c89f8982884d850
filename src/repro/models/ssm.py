"""Mamba-1 selective SSM block (falcon-mamba / jamba mixer).

Faithful mamba-1 recurrence:
  h_t = exp(dt_t * A) ⊙ h_{t-1} + dt_t * B_t * x_t
  y_t = C_t · h_t + D ⊙ x_t
with depthwise causal conv front-end and SiLU gating.  Full-sequence apply
uses ``lax.scan`` (compact HLO; the per-step state (B, d_inner, n) is the
"KV-analogue" payload for attention-free archs — see DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.axes import Initializer, Pm
from repro.models.layers import COMPUTE_DTYPE, init_rmsnorm, rmsnorm


def init_mamba(ini: Initializer, cfg: ModelConfig) -> Dict[str, Pm]:
    d, di, n, r, kc = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                       cfg.ssm_conv)
    s = 1.0 / math.sqrt(d)
    # A initialised to -[1..n] per channel (S4D-real init).
    a_init = np.log(np.broadcast_to(np.arange(1, n + 1, dtype=np.float32), (di, n)))
    p = {
        "in_proj": ini.normal((d, 2 * di), ("embed", "mlp"), scale=s),
        "conv_w": ini.normal((di, kc), ("mlp", None), scale=0.5),
        "conv_b": ini.zeros((di,), ("mlp",)),
        "x_proj": ini.normal((di, r + 2 * n), ("mlp", None),
                             scale=1.0 / math.sqrt(di)),
        "dt_proj_w": ini.normal((r, di), (None, "mlp"), scale=1.0 / math.sqrt(r)),
        "dt_proj_b": ini.constant(
            np.log(np.expm1(np.full((di,), 0.01, dtype=np.float32))), ("mlp",)),
        "a_log": ini.constant(a_init, ("mlp", "state")),
        "d_skip": ini.ones((di,), ("mlp",)),
        "out_proj": ini.normal((di, d), ("mlp", "embed"),
                               scale=1.0 / math.sqrt(di)),
    }
    if cfg.ssm_norms:
        p["dt_norm"] = init_rmsnorm(ini, r)
        p["b_norm"] = init_rmsnorm(ini, n)
        p["c_norm"] = init_rmsnorm(ini, n)
    return p


def _causal_depthwise_conv(x, w, b, state: Optional[jnp.ndarray] = None):
    """x: (B, S, di); w: (di, k). Returns (y, new_state (B, di, k-1))."""
    bsz, s, di = x.shape
    k = w.shape[1]
    xt = jnp.transpose(x, (0, 2, 1))  # (B, di, S)
    if state is None:
        pad = jnp.zeros((bsz, di, k - 1), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, xt], axis=-1)  # (B, di, S+k-1)
    y = jax.lax.conv_general_dilated(
        xp[:, :, None, :],  # (B, di, 1, S+k-1) NCHW
        w.astype(x.dtype)[:, None, None, :],  # (di, 1, 1, k) OIHW
        window_strides=(1, 1), padding="VALID", feature_group_count=di,
    )[:, :, 0, :]  # (B, di, S)
    y = y + b.astype(x.dtype)[None, :, None]
    new_state = xp[:, :, -(k - 1):] if k > 1 else jnp.zeros((bsz, di, 0), x.dtype)
    return jnp.transpose(y, (0, 2, 1)), new_state


def _ssm_params(params, cfg: ModelConfig, x_conv):
    """Input-dependent (dt, B, C) from the conv output; with
    ``cfg.ssm_norms`` (jamba) each of dt (before ``dt_proj``), B and C
    passes an RMSNorm first."""
    n, r = cfg.ssm_state, cfg.dt_rank
    proj = jnp.einsum("bsd,dk->bsk", x_conv.astype(COMPUTE_DTYPE),
                      params["x_proj"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
    dt_r, b_mat, c_mat = jnp.split(proj, [r, r + n], axis=-1)
    if cfg.ssm_norms:
        dt_r = rmsnorm(params["dt_norm"], dt_r, cfg.rmsnorm_eps)
        b_mat = rmsnorm(params["b_norm"], b_mat, cfg.rmsnorm_eps)
        c_mat = rmsnorm(params["c_norm"], c_mat, cfg.rmsnorm_eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_r, params["dt_proj_w"].astype(jnp.float32))
        + params["dt_proj_b"].astype(jnp.float32)
    )  # (B, S, di)
    return dt, b_mat, c_mat


def apply_mamba(
    params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, S, d)
    state: Optional[Dict[str, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (y, new_state).  state = {"ssm": (B, di, n), "conv": (B, di, k-1)}.

    With state given and S small (decode), the same scan path runs the
    recurrence from the carried state."""
    bsz, s, _ = x.shape
    di, n = cfg.d_inner, cfg.ssm_state

    xz = jnp.einsum("bsd,de->bse", x.astype(COMPUTE_DTYPE),
                    params["in_proj"].astype(COMPUTE_DTYPE))
    x_in, z = jnp.split(xz, 2, axis=-1)  # (B, S, di) each

    conv_state = state["conv"] if state is not None else None
    x_conv, new_conv = _causal_depthwise_conv(
        x_in, params["conv_w"], params["conv_b"], conv_state)
    x_conv = jax.nn.silu(x_conv.astype(jnp.float32)).astype(COMPUTE_DTYPE)

    dt, b_mat, c_mat = _ssm_params(params, cfg, x_conv)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))  # (di, n)

    h0 = (state["ssm"].astype(jnp.float32) if state is not None
          else jnp.zeros((bsz, di, n), jnp.float32))

    def step(h, xs):
        xt, dt_t, b_t, c_t = xs  # (B, di), (B, di), (B, n), (B, n)
        da = jnp.exp(dt_t[..., None] * a)  # (B, di, n)
        h = h * da + (dt_t * xt)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = (
        jnp.moveaxis(x_conv.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(b_mat, 1, 0),
        jnp.moveaxis(c_mat, 1, 0),
    )
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B, S, di)
    y = y + x_conv.astype(jnp.float32) * params["d_skip"].astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = jnp.einsum("bsd,de->bse", y.astype(COMPUTE_DTYPE),
                     params["out_proj"].astype(COMPUTE_DTYPE))
    new_state = {"ssm": h_final.astype(jnp.float32), "conv": new_conv}
    return out.astype(x.dtype), new_state


def init_mamba_state(cfg: ModelConfig, batch: int, abstract: bool = False):
    di, n, k = cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    shapes = {
        "ssm": ((batch, di, n), jnp.float32),
        "conv": ((batch, di, k - 1), COMPUTE_DTYPE),
    }
    if abstract:
        return {kk: jax.ShapeDtypeStruct(sh, dt) for kk, (sh, dt) in shapes.items()}
    return {kk: jnp.zeros(sh, dt) for kk, (sh, dt) in shapes.items()}
