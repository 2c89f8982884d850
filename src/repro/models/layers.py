"""Pure-JAX transformer layers: GQA attention (qk-norm, softcap, sliding
window, chunked online-softmax), RoPE / M-RoPE, gated MLP, and sorted
capacity-based MoE.

Everything is functional: ``init_*`` builds Pm-annotated param trees,
``apply_*`` consumes plain value trees.  Compute dtype is bf16 with fp32
softmax/normalisation, matching TPU practice.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.axes import Initializer, Pm

COMPUTE_DTYPE = jnp.bfloat16
ATTN_CHUNK = 1024  # KV chunk for the online-softmax path


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_rmsnorm(ini: Initializer, d: int) -> Dict[str, Pm]:
    return {"scale": ini.ones((d,), (None,))}


def rmsnorm(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def rmsnorm_headdim(scale, x, eps: float = 1e-6):
    """qk-norm: rmsnorm over the head_dim axis of (B, S, H, D)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float):
    """M-RoPE (qwen2-vl): positions3 (3, B, S); freq slots split 2:1:1 over
    (temporal, height, width) position streams."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_frequencies(d, theta)  # (half,)
    t_sec = half // 2
    h_sec = (half - t_sec) // 2
    sec_of = jnp.concatenate([
        jnp.zeros((t_sec,), jnp.int32),
        jnp.ones((h_sec,), jnp.int32),
        jnp.full((half - t_sec - h_sec,), 2, jnp.int32),
    ])  # (half,) -> which position stream each freq slot uses
    # pos_per_slot: (B, S, half)
    pos = jnp.transpose(positions3, (1, 2, 0)).astype(jnp.float32)  # (B,S,3)
    pos_slot = pos[..., sec_of]
    angles = pos_slot * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(ini: Initializer, cfg: ModelConfig) -> Dict[str, Pm]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": ini.normal((d, cfg.num_heads, hd), ("embed", "heads", "head_dim"),
                         scale=1.0 / math.sqrt(d)),
        "wk": ini.normal((d, cfg.kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                         scale=1.0 / math.sqrt(d)),
        "wv": ini.normal((d, cfg.kv_heads, hd), ("embed", "kv_heads", "head_dim"),
                         scale=1.0 / math.sqrt(d)),
        "wo": ini.normal((cfg.num_heads, hd, d), ("heads", "head_dim", "embed"),
                         scale=1.0 / math.sqrt(cfg.num_heads * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = ini.ones((hd,), (None,))
        p["k_norm"] = ini.ones((hd,), (None,))
    return p


def _softcap(x, cap: float):
    if cap and cap > 0:
        return jnp.tanh(x / cap) * cap
    return x


def _mask_value():
    return jnp.finfo(jnp.float32).min


def attention_scores_mask(q_pos, k_pos, causal: bool, window: int,
                          kv_valid: Optional[jnp.ndarray]):
    """Boolean validity mask from position vectors.

    Unbatched ``q_pos`` (Sq,) with scalar ``kv_valid`` yields (Sq, Sk); a
    batched ``q_pos`` (B, Sq) or per-row ``kv_valid`` (B,) yields
    (B, Sq, Sk) — the slot-arena decode path, where every slot sits at its
    own position in its own cache row.
    """
    q = jnp.asarray(q_pos)
    batched = q.ndim == 2 or (kv_valid is not None
                              and jnp.ndim(kv_valid) == 1)
    if batched and q.ndim == 1:
        q = q[None]
    qp = q[..., :, None]                                   # (..., Sq, 1)
    kp = k_pos[None, None, :] if batched else k_pos[None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape[:-1] + (k_pos.shape[-1],),
                                      kp.shape), dtype=bool)
    if causal:
        m = m & (kp <= qp)
    if window and window > 0:
        m = m & (kp > (qp - window))
    if kv_valid is not None:
        kv = jnp.asarray(kv_valid)
        m = m & (kp < (kv[:, None, None] if kv.ndim == 1 else kv))
    return m


def multihead_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Sk, Hkv, D)
    v: jnp.ndarray,  # (B, Sk, Hkv, D)
    *,
    q_positions: jnp.ndarray,  # (Sq,) int32
    k_positions: jnp.ndarray,  # (Sk,) int32
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    kv_valid: Optional[jnp.ndarray] = None,  # scalar: #valid cache slots
    chunk: int = ATTN_CHUNK,
    return_stats: bool = False,
):
    """GQA attention with chunked online softmax over the KV axis.

    The chunked path bounds the score temporaries to (B,H,Sq,chunk) — the
    XLA-side analogue of flash attention (the Pallas kernel in
    ``repro.kernels`` is the TPU hot-path; this is the portable lowering the
    dry-run compiles)."""
    b, sq, hq, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(dh)

    qg = q.reshape(b, sq, hkv, g, dh).astype(COMPUTE_DTYPE)
    k = k.astype(COMPUTE_DTYPE)
    v = v.astype(COMPUTE_DTYPE)

    # Direct path for short KV and for single-query decode: with sq == 1 the
    # score tensor is tiny, and the un-chunked einsum lets GSPMD keep a
    # sequence-sharded KV cache sharded (flash-decoding style partial
    # softmax) instead of "involuntary full rematerialization" of the cache
    # to head sharding (EXPERIMENTS.md §Perf hillclimb #3, iteration 3).
    if sk <= chunk or sq == 1:
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
        scores = _softcap(scores, softcap)
        mask = attention_scores_mask(q_positions, k_positions, causal, window, kv_valid)
        mask = mask if mask.ndim == 3 else mask[None]  # (B|1, Sq, Sk)
        scores = jnp.where(mask[:, None, None], scores, _mask_value())
        if return_stats:
            m = scores.max(axis=-1)
            l = jnp.exp(scores - m[..., None]).sum(axis=-1)
            probs = jnp.exp(scores - m[..., None])
            out = jnp.einsum("bhgqk,bkhd->bhgqd",
                             probs.astype(COMPUTE_DTYPE), v)
            return out, m, l  # out UNNORMALISED (b,h,g,q,dh)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(COMPUTE_DTYPE), v)
        return out.reshape(b, sq, hq, dh)

    # ---- chunked online softmax over KV ----
    # Chunks are read via dynamic_slice inside the loop body (NOT pre-split
    # scan xs): a moveaxis'd xs materialises a transposed full-KV copy per
    # layer, which doubled decode HBM traffic (EXPERIMENTS.md §Perf).
    n_chunks = sk // chunk
    assert sk % chunk == 0, (sk, chunk)

    def body(carry, i):
        m_prev, l_prev, acc = carry
        kc = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(k_positions, i * chunk, chunk, 0)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc).astype(jnp.float32) * scale
        s = _softcap(s, softcap)
        mask = attention_scores_mask(q_positions, kp, causal, window, kv_valid)
        mask = mask if mask.ndim == 3 else mask[None]
        s = jnp.where(mask[:, None, None], s, _mask_value())
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(COMPUTE_DTYPE), vc)
        acc = acc * alpha[..., None].astype(COMPUTE_DTYPE) + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, dh), COMPUTE_DTYPE)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), jnp.arange(n_chunks, dtype=jnp.int32))
    if return_stats:
        # acc is scaled relative to exp(m); hand back raw stats
        return acc, m, l  # (b,h,g,q,dh), (b,h,g,q), (b,h,g,q)
    out = acc / jnp.maximum(l, 1e-30)[..., None].astype(COMPUTE_DTYPE)
    out = jnp.moveaxis(out, 3, 1)  # (b, sq, hkv, g, dh)
    return out.reshape(b, sq, hq, dh)


def apply_attention(
    params,
    cfg: ModelConfig,
    x: jnp.ndarray,  # (B, S, d)
    *,
    positions,  # (B, S) or (3, B, S) for mrope
    causal: bool = True,
    local: bool = False,
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    cache_pos: Optional[jnp.ndarray] = None,  # scalar position for decode
    xattn_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Full attention sublayer. Returns (out, new_cache).

    Modes:
      - training / prefill: cache None -> self attention over x
      - decode: cache {"k","v"} (B, Smax, Hkv, D), cache_pos scalar
      - cross-attention: xattn_kv provides precomputed (k, v)
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    xc = x.astype(COMPUTE_DTYPE)
    q = jnp.einsum("bsd,dhk->bshk", xc, params["wq"].astype(COMPUTE_DTYPE))

    if cfg.qk_norm:
        q = rmsnorm_headdim(params["q_norm"], q, cfg.rmsnorm_eps)

    window = cfg.sliding_window if local else 0

    if xattn_kv is not None:
        k, v = xattn_kv
        k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        q_pos = jnp.arange(s, dtype=jnp.int32)
        out = multihead_attention(
            q, k, v, q_positions=q_pos, k_positions=k_pos, causal=False,
            softcap=cfg.attn_softcap,
        )
        new_cache = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", xc, params["wk"].astype(COMPUTE_DTYPE))
        v = jnp.einsum("bsd,dhk->bshk", xc, params["wv"].astype(COMPUTE_DTYPE))
        if cfg.qk_norm:
            k = rmsnorm_headdim(params["k_norm"], k, cfg.rmsnorm_eps)
        if cfg.mrope:
            q = apply_mrope(q, positions, cfg.rope_theta)
            k = apply_mrope(k, positions, cfg.rope_theta)
        elif cfg.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

        if cache is None:
            pos1 = jnp.arange(s, dtype=jnp.int32)
            out = multihead_attention(
                q, k, v, q_positions=pos1, k_positions=pos1, causal=causal,
                window=window, softcap=cfg.attn_softcap,
            )
            new_cache = {"k": k, "v": v}
        else:
            # Decode: the cache is READ-ONLY here; the new tokens' (k, v)
            # merge in closed form via online-softmax statistics, and the
            # cache update happens once, post-scan, as a single stacked
            # dynamic-update-slice (EXPERIMENTS.md §Perf hillclimb #3 —
            # rewriting the cache through scan ys churned full-cache copies
            # every block iteration).  s == 1 is the plain decode step;
            # s > 1 is the speculative multi-token verify step, where the
            # s new tokens sit at consecutive positions cache_pos..+s-1 and
            # attend to each other under an intra-block causal mask.
            hkv = k.shape[2]
            g = cfg.num_heads // cfg.kv_heads
            smax = cache["k"].shape[1]
            k_pos = jnp.arange(smax, dtype=jnp.int32)
            offs = jnp.arange(s, dtype=jnp.int32)
            if jnp.ndim(cache_pos) == 1:
                # Slot-arena decode: every row sits at its own position.
                q_pos = cache_pos.astype(jnp.int32)[:, None] + offs[None, :]
            else:
                q_pos = jnp.asarray(cache_pos, jnp.int32) + offs  # (S,)
            out_old, m_old, l_old = multihead_attention(
                q, cache["k"], cache["v"], q_positions=q_pos,
                k_positions=k_pos, causal=True, window=window,
                softcap=cfg.attn_softcap, kv_valid=cache_pos,
                return_stats=True,
            )  # (b,h,g,S,dh), (b,h,g,S), (b,h,g,S)
            qg = q.reshape(b, s, hkv, g, hd)
            scale = 1.0 / math.sqrt(hd)
            if s == 1:
                s_new = jnp.einsum("bqhgd,bqhd->bhgq",
                                   qg.astype(COMPUTE_DTYPE),
                                   k.astype(COMPUTE_DTYPE)).astype(jnp.float32)
                s_new = _softcap(s_new * scale, cfg.attn_softcap)
                m_new = jnp.maximum(m_old, s_new)
                alpha = jnp.exp(m_old - m_new)
                p_new = jnp.exp(s_new - m_new)
                v_b = v.reshape(b, 1, hkv, 1, hd).transpose(0, 2, 3, 1, 4)
                num = (out_old.astype(jnp.float32) * alpha[..., None]
                       + p_new[..., None] * v_b.astype(jnp.float32))
                den = l_old * alpha + p_new
                out = (num / jnp.maximum(den, 1e-30)[..., None])
            else:
                # Intra-block attention of the s new tokens over themselves:
                # query row i sees new token j iff j <= i (positions are
                # consecutive, so the sliding window reduces to j > i - w).
                s_blk = jnp.einsum("bqhgd,bjhd->bhgqj",
                                   qg.astype(COMPUTE_DTYPE),
                                   k.astype(COMPUTE_DTYPE)).astype(jnp.float32)
                s_blk = _softcap(s_blk * scale, cfg.attn_softcap)
                blk_ok = offs[None, :] <= offs[:, None]           # (Sq, Sj)
                if window and window > 0:
                    blk_ok = blk_ok & (offs[None, :] > offs[:, None] - window)
                s_blk = jnp.where(blk_ok[None, None, None], s_blk,
                                  _mask_value())
                m_new = jnp.maximum(m_old, s_blk.max(axis=-1))
                alpha = jnp.exp(m_old - m_new)
                p_blk = jnp.exp(s_blk - m_new[..., None])
                pv = jnp.einsum("bhgqj,bjhd->bhgqd", p_blk,
                                v.astype(jnp.float32))
                num = out_old.astype(jnp.float32) * alpha[..., None] + pv
                den = l_old * alpha + p_blk.sum(axis=-1)
                out = num / jnp.maximum(den, 1e-30)[..., None]
            out = jnp.moveaxis(out.astype(COMPUTE_DTYPE), 3, 1)  # (b,S,h,g,dh)
            out = out.reshape(b, s, hkv * g, hd)
            new_cache = {"k_new": k.astype(cache["k"].dtype),
                         "v_new": v.astype(cache["v"].dtype)}

    y = jnp.einsum("bshk,hkd->bsd", out.astype(COMPUTE_DTYPE),
                   params["wo"].astype(COMPUTE_DTYPE))
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------
def init_mlp(ini: Initializer, d: int, d_ff: int) -> Dict[str, Pm]:
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "wi_gate": ini.normal((d, d_ff), ("embed", "mlp"), scale=s_in),
        "wi_up": ini.normal((d, d_ff), ("embed", "mlp"), scale=s_in),
        "wo": ini.normal((d_ff, d), ("mlp", "embed"), scale=s_out),
    }


def apply_mlp(params, x):
    xc = x.astype(COMPUTE_DTYPE)
    g = jnp.einsum("bsd,df->bsf", xc, params["wi_gate"].astype(COMPUTE_DTYPE))
    u = jnp.einsum("bsd,df->bsf", xc, params["wi_up"].astype(COMPUTE_DTYPE))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    y = jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(COMPUTE_DTYPE))
    return y.astype(x.dtype)


def init_moe(ini: Initializer, cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {
        "router": ini.normal((d, e), ("embed", "experts"), scale=s_in),
        "wi_gate": ini.normal((e, d, f), ("experts", "embed", "expert_mlp"), scale=s_in),
        "wi_up": ini.normal((e, d, f), ("experts", "embed", "expert_mlp"), scale=s_in),
        "wo": ini.normal((e, f, d), ("experts", "expert_mlp", "embed"), scale=s_out),
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = init_mlp(ini, d, f * cfg.num_shared_experts)
    return p


def apply_moe(params, cfg: ModelConfig, x):
    """Top-k MoE with capacity. Two implementations:

    - "einsum" (default): GShard-style one-hot dispatch/combine einsums.
      GSPMD partitions these cleanly — dispatch is local per data shard and
      the only collective is one model-axis all-reduce of the combined
      output (EXPERIMENTS.md §Perf hillclimb #2: the sort/scatter path made
      GSPMD replicate + all-reduce the full (T·k, d) token tensor, 169.8s
      of collective time for deepseek train; einsum dispatch removes it).
    - "sort": capacity-sorted scatter/gather (kept for comparison and
      single-device use).

    Returns (y, aux_loss)."""
    if getattr(cfg, "moe_impl", "einsum") == "einsum":
        return _apply_moe_einsum(params, cfg, x)
    return _apply_moe_sort(params, cfg, x)


def _apply_moe_einsum(params, cfg: ModelConfig, x):
    from repro.utils.shard_hint import shard_hint

    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = max(int(math.ceil(s * k / e * cfg.capacity_factor)), 1)
    cap = min(cap, s)

    logits = (x.astype(COMPUTE_DTYPE)
              @ params["router"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (B, S, E)
    top_w, top_ids = jax.lax.top_k(probs, k)  # (B, S, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # (B, S, E) membership and gate weights (experts distinct within top-k)
    onehot = jax.nn.one_hot(top_ids, e, dtype=jnp.float32)  # (B, S, k, E)
    mask = onehot.sum(axis=2)                               # (B, S, E)
    gates_e = (onehot * top_w[..., None]).sum(axis=2)       # (B, S, E)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=(0, 1))
    ce = mask.mean(axis=(0, 1)) / k
    aux = (me * ce).sum() * e

    # position of each token in its expert's queue (earlier tokens win)
    pos = jnp.cumsum(mask, axis=1) - mask                   # (B, S, E)
    keep = (pos < cap) & (mask > 0)

    disp = (keep[..., None]
            * jax.nn.one_hot(pos.astype(jnp.int32), cap,
                             dtype=COMPUTE_DTYPE))          # (B, S, E, C)
    disp = shard_hint(disp, ("pod", "data"), None, "model", None)

    xb = x.astype(COMPUTE_DTYPE)
    buf = jnp.einsum("bsec,bsd->becd", disp, xb)            # (B, E, C, d)
    buf = shard_hint(buf, ("pod", "data"), "model", None, None)

    g = jnp.einsum("becd,edf->becf", buf, params["wi_gate"].astype(COMPUTE_DTYPE))
    u = jnp.einsum("becd,edf->becf", buf, params["wi_up"].astype(COMPUTE_DTYPE))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    yb = jnp.einsum("becf,efd->becd", h, params["wo"].astype(COMPUTE_DTYPE))
    yb = shard_hint(yb, ("pod", "data"), "model", None, None)

    combine = (disp.astype(jnp.float32)
               * gates_e[..., None]).astype(COMPUTE_DTYPE)  # (B, S, E, C)
    y = jnp.einsum("becd,bsec->bsd", yb, combine)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], xb).astype(COMPUTE_DTYPE)
    return y.astype(x.dtype), aux


def _apply_moe_sort(params, cfg: ModelConfig, x):
    """Sorted capacity-based top-k MoE (drop on overflow)."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(cap, 1)

    xt = x.reshape(t, d)
    logits = (xt.astype(COMPUTE_DTYPE)
              @ params["router"].astype(COMPUTE_DTYPE)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_ids = jax.lax.top_k(probs, k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(1.0) / (t * k)
    aux = (me * ce).sum() * e

    flat_ids = top_ids.reshape(-1)  # (T*k,)
    sort_idx = jnp.argsort(flat_ids)
    sorted_ids = flat_ids[sort_idx]
    counts = jnp.bincount(flat_ids, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_expert = jnp.arange(t * k) - starts[sorted_ids]
    keep = pos_in_expert < cap
    slot = sorted_ids * cap + jnp.minimum(pos_in_expert, cap - 1)  # (T*k,)

    token_of = sort_idx // k
    gathered = xt[token_of].astype(COMPUTE_DTYPE) * keep[:, None]
    buf = jnp.zeros((e * cap, d), COMPUTE_DTYPE).at[slot].add(
        jnp.where(keep[:, None], gathered, 0)
    )
    # Expert parallelism: pin the dispatch buffer and expert compute to the
    # model axis so GSPMD lowers the token exchange as an all-to-all instead
    # of replicating/all-gathering the full token set (EXPERIMENTS.md §Perf).
    from repro.utils.shard_hint import shard_hint
    buf = shard_hint(buf.reshape(e, cap, d), "model", None, None)

    g = jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"].astype(COMPUTE_DTYPE))
    u = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"].astype(COMPUTE_DTYPE))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(COMPUTE_DTYPE) * u
    yb = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(COMPUTE_DTYPE))
    yb = shard_hint(yb, "model", None, None)

    y_flat = yb.reshape(e * cap, d)[slot] * keep[:, None]  # (T*k, d)
    w_sorted = top_w.reshape(-1)[sort_idx]
    contrib = (y_flat.astype(jnp.float32) * w_sorted[:, None]).astype(COMPUTE_DTYPE)
    y = jnp.zeros((t, d), COMPUTE_DTYPE).at[token_of].add(contrib)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], xt[None])[0].astype(COMPUTE_DTYPE)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embedding(ini: Initializer, cfg: ModelConfig) -> Dict[str, Pm]:
    p = {"tok": ini.normal((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           scale=1.0)}
    if not cfg.tie_embeddings:
        p["unembed"] = ini.normal((cfg.d_model, cfg.vocab_size),
                                  ("embed", "vocab"),
                                  scale=1.0 / math.sqrt(cfg.d_model))
    return p


def embed_tokens(params, tokens):
    return jnp.take(params["tok"], tokens, axis=0)


def unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        w = params["tok"].T
    else:
        w = params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x.astype(COMPUTE_DTYPE),
                        w.astype(COMPUTE_DTYPE)).astype(jnp.float32)
    return _softcap(logits, cfg.final_softcap)
