"""Unified model stack: dense / MoE / SSM / hybrid decoders and the whisper
encoder-decoder, built as (optional prefix layers) + scan-over-layer-blocks.

Scan-over-layers keeps the HLO size O(period) instead of O(num_layers) —
essential for compiling 80-layer configs in the multi-pod dry-run.  Hybrid
archs (jamba: 1 attn per 8 layers, MoE every 2) scan over their repeating
period; irregular prefixes (deepseek's dense first layer) sit outside the
scan.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig, repeat_period
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.axes import Initializer, Pm, abstract_like_block, is_pm, split_tree, stack_block_params

COMPUTE_DTYPE = L.COMPUTE_DTYPE


# ---------------------------------------------------------------------------
# Structure resolution
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class StackPlan:
    prefix_specs: Tuple[LayerSpec, ...]
    period_specs: Tuple[LayerSpec, ...]
    n_blocks: int


def plan_stack(cfg: ModelConfig) -> StackPlan:
    specs = cfg.layer_specs()
    # Pull an irregular prefix (e.g. deepseek dense first layer[s]) out front.
    for prefix_len in range(0, min(len(specs), 4)):
        rest = specs[prefix_len:]
        period = repeat_period(rest, cfg.layer_periods())
        if period:
            return StackPlan(tuple(specs[:prefix_len]), tuple(rest[:period]),
                             len(rest) // period)
    # Fully irregular: everything is prefix (no scan).
    return StackPlan(tuple(specs), (), 0)


# ---------------------------------------------------------------------------
# Layer init / apply
# ---------------------------------------------------------------------------
def _init_layer(ini: Initializer, cfg: ModelConfig, spec: LayerSpec,
                cross_attn: bool = False) -> Dict[str, Any]:
    p: Dict[str, Any] = {"ln1": L.init_rmsnorm(ini, cfg.d_model)}
    if spec.kind == "attn":
        p["mixer"] = L.init_attention(ini, cfg)
    else:
        p["mixer"] = S.init_mamba(ini, cfg)
    if cross_attn:
        p["lnx"] = L.init_rmsnorm(ini, cfg.d_model)
        p["xattn"] = L.init_attention(ini, cfg)
    if cfg.d_ff > 0:
        p["ln2"] = L.init_rmsnorm(ini, cfg.d_model)
        p["mlp"] = L.init_moe(ini, cfg) if spec.moe else L.init_mlp(
            ini, cfg.d_model, cfg.d_ff)
    return p


def _apply_layer(
    lp, cfg: ModelConfig, spec: LayerSpec, x, *,
    positions, mode: str, cache=None, cache_pos=None, max_len: int = 0,
    xattn_kv=None, cross_attn: bool = False,
):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(lp["ln1"], x, cfg.rmsnorm_eps)

    if spec.kind == "attn":
        if mode == "decode":
            y, new_cache = L.apply_attention(
                lp["mixer"], cfg, h, positions=positions, local=spec.local,
                cache=cache, cache_pos=cache_pos)
        else:
            y, kv = L.apply_attention(
                lp["mixer"], cfg, h, positions=positions, local=spec.local,
                causal=not (cross_attn is False and cfg.encoder_decoder and mode == "encode"))
            new_cache = None
            if mode == "prefill":
                new_cache = _pad_kv(kv, max_len)
    else:
        state = cache if mode == "decode" else None
        y, new_state = S.apply_mamba(lp["mixer"], cfg, h, state=state)
        new_cache = new_state if mode in ("decode", "prefill") else None
    x = x + y

    if cross_attn:
        hx = L.rmsnorm(lp["lnx"], x, cfg.rmsnorm_eps)
        yx, _ = L.apply_attention(
            lp["xattn"], cfg, hx, positions=positions, xattn_kv=xattn_kv)
        x = x + yx

    if cfg.d_ff > 0:
        h2 = L.rmsnorm(lp["ln2"], x, cfg.rmsnorm_eps)
        if spec.moe:
            y2, aux = L.apply_moe(lp["mlp"], cfg, h2)
        else:
            y2 = L.apply_mlp(lp["mlp"], h2)
        x = x + y2
    return x, new_cache, aux


def _pad_kv(kv, max_len: int):
    """Pad prefill k/v (B, S, H, D) along seq to max_len cache slots."""
    if max_len <= 0:
        return kv
    out = {}
    for key in ("k", "v"):
        arr = kv[key]
        s = arr.shape[1]
        if s < max_len:
            pad = jnp.zeros((arr.shape[0], max_len - s) + arr.shape[2:], arr.dtype)
            arr = jnp.concatenate([arr, pad], axis=1)
        out[key] = arr.astype(COMPUTE_DTYPE)
    return out


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, seed: int = 0, abstract: bool = False,
                dtype=None):
    """Returns (values_tree, axes_tree).  dtype=bf16 for serving-only params
    (halves weight HBM traffic; training keeps fp32 masters)."""
    import jax.numpy as _jnp
    ini = Initializer(seed=seed, abstract=abstract,
                      dtype=dtype or _jnp.float32)
    plan = plan_stack(cfg)
    params: Dict[str, Any] = {
        "embed": L.init_embedding(ini, cfg),
        "final_norm": L.init_rmsnorm(ini, cfg.d_model),
    }
    cross = cfg.encoder_decoder
    params["prefix"] = {
        f"layer{i}": _init_layer(ini, cfg, spec, cross_attn=cross)
        for i, spec in enumerate(plan.prefix_specs)
    }
    if plan.n_blocks > 0:
        block = {
            f"layer{j}": _init_layer(ini, cfg, spec, cross_attn=cross)
            for j, spec in enumerate(plan.period_specs)
        }
        if abstract:
            params["blocks"] = abstract_like_block(block, plan.n_blocks)
        else:
            blocks = []
            for b in range(plan.n_blocks):
                ini_b = Initializer(seed=seed * 1000 + b + 1, abstract=False)
                blocks.append({
                    f"layer{j}": _init_layer(ini_b, cfg, spec, cross_attn=cross)
                    for j, spec in enumerate(plan.period_specs)
                })
            params["blocks"] = stack_block_params(blocks)
    if cfg.encoder_decoder:
        params["encoder"] = _init_encoder(ini, cfg)
    return split_tree(params)


def _init_encoder(ini: Initializer, cfg: ModelConfig) -> Dict[str, Any]:
    spec = LayerSpec(kind="attn", moe=False, local=False)
    block = {"layer0": _init_layer(ini, cfg, spec)}
    return {
        "blocks": (abstract_like_block(block, cfg.enc_layers)
                   if ini.abstract else stack_block_params(
                       [{"layer0": _init_layer(
                           Initializer(seed=7000 + b, abstract=False), cfg, spec)}
                        for b in range(cfg.enc_layers)])),
        "final_norm": L.init_rmsnorm(ini, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Stack application
# ---------------------------------------------------------------------------
def _run_stack(params, cfg: ModelConfig, plan: StackPlan, x, *, positions,
               mode: str, caches=None, cache_pos=None, max_len: int = 0,
               xattn_kv=None, remat: bool = False):
    """Run prefix + scanned blocks. Returns (x, new_caches, aux_total).

    caches/new_caches structure:
      {"prefix": {"layer{i}": cache_i}, "blocks": {"layer{j}": stacked}}
    """
    cross = cfg.encoder_decoder and xattn_kv is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_prefix = {}
    for i, spec in enumerate(plan.prefix_specs):
        name = f"layer{i}"
        c_in = caches["prefix"][name] if caches is not None else None
        xkv_i = None
        if cross:
            xkv_i = (xattn_kv["prefix"][name]["xk"], xattn_kv["prefix"][name]["xv"])
        x, c_out, aux = _apply_layer(
            params["prefix"][name], cfg, spec, x, positions=positions,
            mode=mode, cache=c_in, cache_pos=cache_pos, max_len=max_len,
            xattn_kv=xkv_i, cross_attn=cross)
        aux_total = aux_total + aux
        if c_out is not None:
            new_prefix[name] = c_out

    new_blocks = None
    if plan.n_blocks > 0:
        def body(carry, xs):
            xc, auxc = carry
            if mode == "decode":
                bp, bc, bxkv = xs
            elif cross:
                bp, bxkv = xs
                bc = None
            else:
                bp = xs
                bc, bxkv = None, None
            block_caches = {}
            for j, spec in enumerate(plan.period_specs):
                name = f"layer{j}"
                c_in = bc[name] if bc is not None else None
                xkv_j = (bxkv[name]["xk"], bxkv[name]["xv"]) if cross else None
                xc, c_out, aux = _apply_layer(
                    bp[name], cfg, spec, xc, positions=positions, mode=mode,
                    cache=c_in, cache_pos=cache_pos, max_len=max_len,
                    xattn_kv=xkv_j, cross_attn=cross)
                auxc = auxc + aux
                if c_out is not None:
                    block_caches[name] = c_out
            ys = block_caches if block_caches else None
            return (xc, auxc), ys

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)

        if mode == "decode":
            xs = (params["blocks"], caches["blocks"],
                  xattn_kv["blocks"] if cross else _none_like(params["blocks"]))
        elif cross:
            xs = (params["blocks"], xattn_kv["blocks"])
        else:
            xs = params["blocks"]
        (x, aux_total), new_blocks = jax.lax.scan(body, (x, aux_total), xs)

    new_caches = None
    if mode in ("prefill", "decode"):
        new_caches = {"prefix": new_prefix, "blocks": new_blocks or {}}
        if mode == "decode":
            new_caches = _merge_decode_updates(new_caches, caches, cache_pos)
    return x, new_caches, aux_total


def _merge_decode_updates(new_caches, caches, cache_pos):
    """Write the per-layer (k_new, v_new) token slices into the full cache
    buffers with ONE dynamic-update-slice per (stacked) buffer.

    A vector ``cache_pos`` (B,) writes each batch row at its own position
    (slot-arena decode): one dynamic-update-slice per row via vmap, which
    XLA lowers to a scatter over the batch axis."""
    per_slot = jnp.ndim(cache_pos) == 1

    def _row_write(b_old, upd, p):
        # b_old (Smax, H, D); upd (S, H, D) — S consecutive rows from p
        return jax.lax.dynamic_update_slice(b_old, upd, (p,) + (0,) * (b_old.ndim - 1))

    def _merge(sub, old, stacked: bool):
        out = {}
        for name, c in sub.items():
            if isinstance(c, dict) and "k_new" in c:
                buf = {}
                for key, nk in (("k", "k_new"), ("v", "v_new")):
                    b_old = old[name][key]
                    upd = c[nk].astype(b_old.dtype)
                    if per_slot:
                        row = jax.vmap(_row_write)
                        if stacked:  # (N, B, S, H, D)
                            buf[key] = jax.vmap(
                                lambda bo, up: row(bo, up, cache_pos))(b_old, upd)
                        else:        # (B, S, H, D)
                            buf[key] = row(b_old, upd, cache_pos)
                    else:
                        idx = ((0, 0, cache_pos, 0, 0) if stacked
                               else (0, cache_pos, 0, 0))
                        buf[key] = jax.lax.dynamic_update_slice(b_old, upd, idx)
                out[name] = buf
            else:
                out[name] = c  # mamba state: carried whole (it is small)
        return out

    return {
        "prefix": _merge(new_caches["prefix"], caches["prefix"], False),
        "blocks": _merge(new_caches["blocks"], caches["blocks"], True),
    }


def _none_like(tree):
    # scan xs placeholder aligned with blocks' leading dim
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    return jnp.zeros((n, 1), jnp.int8)


# ---------------------------------------------------------------------------
# Embedding helpers / positions
# ---------------------------------------------------------------------------
def _positions_for(cfg: ModelConfig, batch: Dict[str, Any], seq: int, bsz: int):
    if cfg.mrope:
        if "positions" in batch:
            return batch["positions"]
        p = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, None],
                             (3, bsz, seq))
        return p
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None], (bsz, seq))


def _embed_inputs(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    x = L.embed_tokens(params["embed"], batch["tokens"]).astype(COMPUTE_DTYPE)
    if cfg.vision_prefix_frac > 0 and "patch_embeds" in batch:
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(COMPUTE_DTYPE), x], axis=1)
    return x


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch, remat: bool = False):
    """Full-sequence forward (training). Returns (logits, aux)."""
    plan = plan_stack(cfg)
    if cfg.encoder_decoder:
        return _forward_encdec(cfg, params, batch, plan, remat)
    x = _embed_inputs(params, cfg, batch)
    bsz, seq = x.shape[0], x.shape[1]
    positions = _positions_for(cfg, batch, seq, bsz)
    x, _, aux = _run_stack(params, cfg, plan, x, positions=positions,
                           mode="train", remat=remat)
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = L.unembed(params["embed"], cfg, x)
    return logits, aux


def _encode(cfg: ModelConfig, params, frames):
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    bsz, s, _ = frames.shape
    # Sinusoidal positions (whisper encoder).
    d = cfg.d_model
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    x = frames.astype(COMPUTE_DTYPE) + pe.astype(COMPUTE_DTYPE)[None]

    spec = LayerSpec(kind="attn", moe=False, local=False)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (bsz, s))

    def body(carry, bp):
        xc = carry
        xc, _, _ = _apply_layer(bp["layer0"], cfg, spec, xc,
                                positions=positions, mode="encode")
        return xc, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    return L.rmsnorm(params["encoder"]["final_norm"], x, cfg.rmsnorm_eps)


def _cross_kv(cfg: ModelConfig, params, enc_out):
    """Precompute per-decoder-layer cross-attention K/V from encoder output."""
    plan = plan_stack(cfg)

    def kv_of(lp):
        xc = enc_out.astype(COMPUTE_DTYPE)
        k = jnp.einsum("bsd,dhk->bshk", xc, lp["xattn"]["wk"].astype(COMPUTE_DTYPE))
        v = jnp.einsum("bsd,dhk->bshk", xc, lp["xattn"]["wv"].astype(COMPUTE_DTYPE))
        return {"xk": k, "xv": v}

    prefix = {f"layer{i}": kv_of(params["prefix"][f"layer{i}"])
              for i in range(len(plan.prefix_specs))}
    blocks = None
    if plan.n_blocks > 0:
        def body(_, bp):
            return None, {f"layer{j}": kv_of(bp[f"layer{j}"])
                          for j in range(len(plan.period_specs))}
        _, blocks = jax.lax.scan(body, None, params["blocks"])
    return {"prefix": prefix, "blocks": blocks or {}}


def _forward_encdec(cfg: ModelConfig, params, batch, plan: StackPlan, remat):
    enc_out = _encode(cfg, params, batch["frames"])
    xattn_kv = _cross_kv(cfg, params, enc_out)
    tokens = batch["tokens"]
    bsz, seq = tokens.shape
    x = L.embed_tokens(params["embed"], tokens).astype(COMPUTE_DTYPE)
    positions = _positions_for(cfg, batch, seq, bsz)
    x, _, aux = _run_stack(params, cfg, plan, x, positions=positions,
                           mode="train", xattn_kv=xattn_kv, remat=remat)
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    return L.unembed(params["embed"], cfg, x), aux


def prefill(cfg: ModelConfig, params, batch, max_len: int):
    """Prompt processing. Returns (last_token_logits, caches)."""
    plan = plan_stack(cfg)
    xattn_kv = None
    if cfg.encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        xattn_kv = _cross_kv(cfg, params, enc_out)
        x = L.embed_tokens(params["embed"], batch["tokens"]).astype(COMPUTE_DTYPE)
    else:
        x = _embed_inputs(params, cfg, batch)
    bsz, seq = x.shape[0], x.shape[1]
    positions = _positions_for(cfg, batch, seq, bsz)
    x, caches, _ = _run_stack(params, cfg, plan, x, positions=positions,
                              mode="prefill", max_len=max_len,
                              xattn_kv=xattn_kv)
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = L.unembed(params["embed"], cfg, x[:, -1:, :])
    if cfg.encoder_decoder:
        caches = {"self": caches, "cross": xattn_kv}
    return logits, caches


def decode_step(cfg: ModelConfig, params, caches, tokens, pos):
    """One decode step. tokens (B, S); pos scalar int32 (next slot index),
    or (B,) int32 for the batched slot arena, where every cache row sits at
    its own position (ragged continuous-batching decode).  S == 1 is the
    plain step; S > 1 is the speculative multi-token verify step — the S
    tokens occupy consecutive positions pos..pos+S-1 and the logits come
    back for every position."""
    plan = plan_stack(cfg)
    xattn_kv = None
    if cfg.encoder_decoder:
        xattn_kv = caches["cross"]
        self_caches = caches["self"]
    else:
        self_caches = caches
    x = L.embed_tokens(params["embed"], tokens).astype(COMPUTE_DTYPE)
    bsz, s = x.shape[0], x.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    offs = jnp.arange(s, dtype=jnp.int32)
    if cfg.mrope:
        base = pos[None, :, None] if pos.ndim == 1 else pos
        positions = jnp.broadcast_to(base + offs[None, None, :], (3, bsz, s))
    else:
        positions = ((pos[:, None] + offs[None, :]) if pos.ndim == 1
                     else jnp.broadcast_to(pos + offs, (bsz, s)))
    x, new_caches, _ = _run_stack(params, cfg, plan, x, positions=positions,
                                  mode="decode", caches=self_caches,
                                  cache_pos=pos, xattn_kv=xattn_kv)
    x = L.rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    logits = L.unembed(params["embed"], cfg, x)
    if cfg.encoder_decoder:
        new_caches = {"self": new_caches, "cross": xattn_kv}
    return logits, new_caches


# ---------------------------------------------------------------------------
# Cache construction (for dry-run decode cells and the serving engine)
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0,
               abstract: bool = False):
    """Build an (abstract) cache pytree for decode-mode lowering."""
    plan = plan_stack(cfg)
    hd = cfg.resolved_head_dim

    def attn_cache():
        shape = (batch, max_len, cfg.kv_heads, hd)
        if abstract:
            return {"k": jax.ShapeDtypeStruct(shape, COMPUTE_DTYPE),
                    "v": jax.ShapeDtypeStruct(shape, COMPUTE_DTYPE)}
        return {"k": jnp.zeros(shape, COMPUTE_DTYPE),
                "v": jnp.zeros(shape, COMPUTE_DTYPE)}

    def layer_cache(spec: LayerSpec):
        if spec.kind == "attn":
            return attn_cache()
        return S.init_mamba_state(cfg, batch, abstract=abstract)

    def lift(tree, n):
        def _l(x):
            if abstract:
                return jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype)
            return jnp.broadcast_to(x[None], (n,) + tuple(x.shape)).copy() \
                if hasattr(x, "shape") else x
        return jax.tree_util.tree_map(_l, tree)

    prefix = {f"layer{i}": layer_cache(spec)
              for i, spec in enumerate(plan.prefix_specs)}
    blocks = {}
    if plan.n_blocks > 0:
        one = {f"layer{j}": layer_cache(spec)
               for j, spec in enumerate(plan.period_specs)}
        blocks = lift(one, plan.n_blocks)
    caches = {"prefix": prefix, "blocks": blocks}

    if cfg.encoder_decoder:
        xshape = (batch, enc_len or max_len, cfg.kv_heads, hd)
        def xkv():
            if abstract:
                return {"xk": jax.ShapeDtypeStruct(xshape, COMPUTE_DTYPE),
                        "xv": jax.ShapeDtypeStruct(xshape, COMPUTE_DTYPE)}
            return {"xk": jnp.zeros(xshape, COMPUTE_DTYPE),
                    "xv": jnp.zeros(xshape, COMPUTE_DTYPE)}
        xprefix = {f"layer{i}": xkv() for i in range(len(plan.prefix_specs))}
        xblocks = {}
        if plan.n_blocks > 0:
            xone = {f"layer{j}": xkv() for j in range(len(plan.period_specs))}
            xblocks = lift(xone, plan.n_blocks)
        caches = {"self": caches, "cross": {"prefix": xprefix, "blocks": xblocks}}
    return caches
