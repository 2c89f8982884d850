"""Pallas TPU kernel: paged quantized decode attention (DESIGN.md §12).

The paged decode arena stores KV as fixed-size pages in a shared pool;
each serving slot owns an ordered list of page ids (its block-table
row).  This kernel gathers a slot's pages straight out of the pool via
scalar-prefetch block-table indexing (``PrefetchScalarGridSpec``) and
fuses int8 / packed-int4 dequantization into the flash-decoding
online-softmax loop — the paged analogue of ``decode_attention.py`` —
so compressed pages are consumed in place and never materialize as
bf16 in HBM.

Grid: (B, Hkv, PPS).  Pages are the innermost (sequential) axis; the
running max / denominator / accumulator persist in VMEM scratch across
pages.  The flattened block table and the per-slot lengths ride ahead
of the grid in SMEM (``num_scalar_prefetch=2``) so the pool BlockSpecs
can do the data-dependent page lookup in their index maps.

Unmapped block-table entries point at page 0 — the arena's reserved
scratch page, never allocated to a slot — and every position they
cover lies at or beyond ``kv_lens[b]``, so the mask sends those scores
to -inf before the softmax: whatever the scratch page holds contributes
exactly zero.  ``kv_lens`` must be >= 1 per row (a fully masked row
would push NaN through the running max, same contract as
``decode_attention``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_pack import F32_DOT, dequant_tile


def _paged_attn_kernel(bt_ref, kvl_ref, q_ref, kc_ref, ks_ref, vc_ref,
                       vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       bits: int, page_size: int,
                       sm_scale: float):
    del bt_ref  # consumed by the BlockSpec index maps, not the body
    b_idx = pl.program_id(0)
    p_idx = pl.program_id(2)
    n_p = pl.num_programs(2)
    kv_len = kvl_ref[b_idx]

    @pl.when(p_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = dequant_tile(kc_ref[0, 0], ks_ref[0, 0], bits)  # (PS, D) f32
    v = dequant_tile(vc_ref[0, 0], vs_ref[0, 0], bits)
    q = q_ref[0, 0].astype(jnp.float32)  # (Gq, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32) * sm_scale  # (Gq, PS)

    # Mask positions at/beyond this slot's length (covers scratch pages).
    base = p_idx * page_size
    pos = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < kv_len, scores, -jnp.inf)

    m_prev = m_scr[...]           # (Gq, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)   # (Gq, PS)
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=F32_DOT,
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(p_idx == n_p - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(
    q: jnp.ndarray,             # (B, Hkv, Gq, D)
    k_codes: jnp.ndarray,       # (P, Hkv, PS, D) int8 or (P, Hkv, PS, D/2) u8
    k_scale: jnp.ndarray,       # (P, Hkv, PS, D/group) f32
    v_codes: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, PPS) int32 page ids; 0 = unmapped
    kv_lens: jnp.ndarray,       # (B,) int32 valid lengths, each >= 1
    *,
    bits: int = 8,
    group: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention of one new token per slot against paged quantized KV.

    Logical position ``t`` of slot ``b`` lives at row ``t % PS`` of pool
    page ``block_tables[b, t // PS]``.  The block table and lengths are
    traced (scalar-prefetched), so page churn never recompiles.
    """
    b, hkv, gq, d = q.shape
    p_total, hkv_k, ps, cw = k_codes.shape
    assert hkv_k == hkv, (hkv_k, hkv)
    assert cw == (d if bits == 8 else d // 2), (cw, d, bits)
    ng = k_scale.shape[3]
    assert ng * group == d, (ng, group, d)
    pps = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_attn_kernel, bits=bits,
                               page_size=ps, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pps),
        in_specs=[
            pl.BlockSpec((1, 1, gq, d),
                         lambda i, j, p, bt, kvl: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, ps, cw),
                         lambda i, j, p, bt, kvl: (bt[i, p], j, 0, 0)),
            pl.BlockSpec((1, 1, ps, ng),
                         lambda i, j, p, bt, kvl: (bt[i, p], j, 0, 0)),
            pl.BlockSpec((1, 1, ps, cw),
                         lambda i, j, p, bt, kvl: (bt[i, p], j, 0, 0)),
            pl.BlockSpec((1, 1, ps, ng),
                         lambda i, j, p, bt, kvl: (bt[i, p], j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gq, d),
                               lambda i, j, p, bt, kvl: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),   # running max
            pltpu.VMEM((gq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((gq, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gq, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32),
      q, k_codes, k_scale, v_codes, v_scale)
