"""Pallas TPU kernel: quantized flash-decode attention (beyond-paper, §7.2
of DESIGN.md).

Decode is memory-bound: each step streams the whole KV cache from HBM.  The
paper decompresses KV to BF16 *before* attention; this kernel instead reads
int8 / packed-int4 KV directly and dequantizes in VMEM inside the online-
softmax loop — HBM traffic drops by ≈16/bits with zero extra passes.

Grid: (B, Hkv, S/BS).  The S axis is the innermost (sequential) dimension;
running max / denominator / accumulator live in VMEM scratch and persist
across S blocks (standard flash-decoding).  The Gq query rows of one GQA
group ride together so the (Gq × D) @ (D × BS) score matmul feeds the MXU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_pack import F32_DOT, dequant_tile


def _attn_kernel(q_ref, kc_ref, ks_ref, vc_ref, vs_ref, *rest,
                 bits: int, kv_len: Optional[int],
                 block_s: int, sm_scale: float):
    if kv_len is None:
        # Multi-slot decode: per-row valid lengths streamed in via SMEM —
        # each batch program masks against its own slot's length.
        kvl_ref, o_ref, m_scr, l_scr, acc_scr = rest
        kv_len = kvl_ref[pl.program_id(0)]
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k = dequant_tile(kc_ref[0, 0], ks_ref[0, 0], bits)  # (BS, D) f32
    v = dequant_tile(vc_ref[0, 0], vs_ref[0, 0], bits)
    q = q_ref[0, 0].astype(jnp.float32)  # (Gq, D)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32) * sm_scale  # (Gq, BS)

    # mask out cache slots beyond kv_len
    base = s_idx * block_s
    pos = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < kv_len, scores, -jnp.inf)

    m_prev = m_scr[...]           # (Gq, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)   # (Gq, BS)
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=F32_DOT,
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(s_idx == n_s - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,        # (B, Hkv, Gq, D)
    k_codes: jnp.ndarray,  # (B, Hkv, S, D) int8  or (B, Hkv, S, D/2) uint8
    k_scale: jnp.ndarray,  # (B, Hkv, S, D/group) f32
    v_codes: jnp.ndarray,
    v_scale: jnp.ndarray,
    *,
    bits: int = 8,
    group: int = 64,
    kv_len=None,           # None | int | (B,) int32 per-slot valid lengths
    block_s: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Quantized flash-decode attention.

    ``kv_len`` as a static int masks every row at the same length (the
    single-sequence decode of PR 1); a (B,) int32 array is the slot-arena
    path — each batch row is one serving slot at its own ragged length,
    masked inside the kernel from an SMEM-resident length vector.
    """
    b, hkv, gq, d = q.shape
    s = k_codes.shape[2]
    bs = min(block_s, s)
    assert s % bs == 0, (s, bs)
    cw = k_codes.shape[3]
    ng = k_scale.shape[3]
    assert ng * group == d, (ng, group, d)
    sm_scale = 1.0 / math.sqrt(d)

    multi_slot = kv_len is not None and jnp.ndim(kv_len) == 1
    static_len = s if kv_len is None else (None if multi_slot else int(kv_len))

    kernel = functools.partial(
        _attn_kernel, bits=bits, kv_len=static_len, block_s=bs,
        sm_scale=sm_scale)

    in_specs = [
        pl.BlockSpec((1, 1, gq, d), lambda i, j, k: (i, j, 0, 0)),
        pl.BlockSpec((1, 1, bs, cw), lambda i, j, k: (i, j, k, 0)),
        pl.BlockSpec((1, 1, bs, ng), lambda i, j, k: (i, j, k, 0)),
        pl.BlockSpec((1, 1, bs, cw), lambda i, j, k: (i, j, k, 0)),
        pl.BlockSpec((1, 1, bs, ng), lambda i, j, k: (i, j, k, 0)),
    ]
    args = [q, k_codes, k_scale, v_codes, v_scale]
    if multi_slot:
        assert kv_len.shape == (b,), (kv_len.shape, b)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(kv_len, jnp.int32))

    return pl.pallas_call(
        kernel,
        grid=(b, hkv, s // bs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, gq, d), lambda i, j, k: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, gq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gq, 1), jnp.float32),   # running max
            pltpu.VMEM((gq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((gq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(*args)
