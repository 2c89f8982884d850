"""Pallas TPU kernel: paged multi-token *verify* attention (DESIGN.md §15).

The speculative-decode verify step feeds ``W`` consecutive query tokens
per slot — the last committed token plus ``W-1`` draft tokens — against
the slot's paged quantized KV.  This is ``paged_attention.py`` widened
with a q-tile axis: the grid and online-softmax page loop are identical,
but the query block carries ``W x Gq`` rows and the length mask becomes
*per query row*.  Query ``j`` of slot ``b`` sits at absolute position
``kv_lens[b] - 1 + j`` (``kv_lens`` counts the committed prefix PLUS the
already-scattered verify rows' first position; see below), so it may
attend cache positions ``< kv_lens[b] + j`` — the staircase causal mask
that keeps each draft position blind to its successors.  Rejected
suffixes therefore never influence any accepted output row: acceptance
is decided on the host purely from the returned rows, and the rejected
positions' KV pages are rolled back by ``PageTable.release_tail``.

Contract: the ``W`` new tokens' own K/V rows are already scattered into
the pages at positions ``kv_lens[b]-1 .. kv_lens[b]+W-2`` (the caller
writes KV before attention, as the arena does), and ``kv_lens[b] >= 1``.
Unmapped block-table entries point at scratch page 0; every position
they cover lies beyond the mask, so their content contributes zero.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_pack import F32_DOT, dequant_tile


def _paged_verify_kernel(bt_ref, kvl_ref, q_ref, kc_ref, ks_ref, vc_ref,
                         vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         bits: int, page_size: int, gq: int,
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
    q = q_ref[0, 0].astype(jnp.float32)  # (W*Gq, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32) * sm_scale  # (W*Gq, PS)

    # Staircase causal mask: query row r belongs to verify position
    # q_idx = r // Gq and sees cache positions < kv_len + q_idx (which
    # also sends every scratch-page position to -inf).
    base = p_idx * page_size
    pos = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // gq
    scores = jnp.where(pos < kv_len + q_idx, scores, -jnp.inf)

    m_prev = m_scr[...]           # (W*Gq, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)   # (W*Gq, PS)
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


def paged_verify_attention(
    q: jnp.ndarray,             # (B, Hkv, W, Gq, D)
    k_codes: jnp.ndarray,       # (P, Hkv, PS, D) int8 or (P, Hkv, PS, D/2) u8
    k_scale: jnp.ndarray,       # (P, Hkv, PS, D/group) f32
    v_codes: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, PPS) int32 page ids; 0 = unmapped
    kv_lens: jnp.ndarray,       # (B,) int32; query 0's visible length, >= 1
    *,
    bits: int = 8,
    group: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Verify attention of ``W`` consecutive tokens per slot against paged
    quantized KV.  Query ``j`` attends positions ``< kv_lens[b] + j``
    (its own already-scattered row included).  Returns (B, Hkv, W, Gq, D).
    """
    b, hkv, w, gq, d = q.shape
    p_total, hkv_k, ps, cw = k_codes.shape
    assert hkv_k == hkv, (hkv_k, hkv)
    assert cw == (d if bits == 8 else d // 2), (cw, d, bits)
    ng = k_scale.shape[3]
    assert ng * group == d, (ng, group, d)
    pps = block_tables.shape[1]
    sm_scale = 1.0 / math.sqrt(d)
    rows = w * gq

    kernel = functools.partial(_paged_verify_kernel, bits=bits,
                               page_size=ps, gq=gq, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, pps),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d),
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
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda i, j, p, bt, kvl: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),   # running max
            pltpu.VMEM((rows, 1), jnp.float32),   # running denominator
            pltpu.VMEM((rows, d), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32),
      q.reshape(b, hkv, rows, d), k_codes, k_scale, v_codes, v_scale)
    return out.reshape(b, hkv, w, gq, d)
