"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

All kernels are validated against these in interpret mode across
shape/dtype sweeps (tests/test_kernels_*.py).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Group quantization (symmetric, per-group along the last axis)
# ---------------------------------------------------------------------------
def quantize_ref(x: jnp.ndarray, bits: int, group: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (..., D) -> (codes int8 (..., D), scales f32 (..., D/group))."""
    d = x.shape[-1]
    assert d % group == 0
    qmax = (1 << (bits - 1)) - 1
    xg = x.reshape(x.shape[:-1] + (d // group, group)).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xg), axis=-1)
    scale = jnp.maximum(amax / qmax, 1e-8)
    q = jnp.clip(jnp.round(xg / scale[..., None]), -qmax - 1, qmax)
    return q.reshape(x.shape).astype(jnp.int8), scale


def dequantize_ref(codes: jnp.ndarray, scale: jnp.ndarray, group: int,
                   dtype=jnp.float32) -> jnp.ndarray:
    d = codes.shape[-1]
    qg = codes.reshape(codes.shape[:-1] + (d // group, group)).astype(jnp.float32)
    x = qg * scale[..., None].astype(jnp.float32)
    return x.reshape(codes.shape).astype(dtype)


def pack_int4_ref(codes: jnp.ndarray) -> jnp.ndarray:
    """int8 codes in [-8,7] -> packed uint8 (last dim halved).

    Half-split layout: the low nibbles hold channels ``[0, D/2)`` and the
    high nibbles ``[D/2, D)``.  Both halves are contiguous lane slices, which
    Mosaic lowers; a lane-strided even/odd interleave it does not."""
    u = (codes.astype(jnp.int32) + 8).astype(jnp.uint8)
    h = u.shape[-1] // 2
    return (u[..., :h] | (u[..., h:] << 4)).astype(jnp.uint8)


def unpack_int4_ref(packed: jnp.ndarray) -> jnp.ndarray:
    lo = (packed & jnp.uint8(0x0F)).astype(jnp.int32) - 8
    hi = (packed >> jnp.uint8(4)).astype(jnp.int32) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def quant_pack_ref(x: jnp.ndarray, bits: int, group: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for ops.quant_pack_op: group-quantize, then pack to nibbles
    when bits == 4 (int8 codes pass through)."""
    codes, scale = quantize_ref(x, bits, group)
    if bits == 4:
        codes = pack_int4_ref(codes)
    return codes, scale


def dequant_unpack_ref(codes: jnp.ndarray, scale: jnp.ndarray, bits: int,
                       group: int, dtype=jnp.float32) -> jnp.ndarray:
    """Oracle for ops.dequant_unpack_op: unpack nibbles when bits == 4,
    then dequantize."""
    if bits == 4:
        codes = unpack_int4_ref(codes)
    return dequantize_ref(codes, scale, group, dtype=dtype)


# ---------------------------------------------------------------------------
# Hadamard transform (orthonormal; D power of two)
# ---------------------------------------------------------------------------
def hadamard_matrix(n: int, dtype=jnp.float32) -> jnp.ndarray:
    assert n & (n - 1) == 0
    h = jnp.array([[1.0]], dtype=jnp.float32)
    while h.shape[0] < n:
        h = jnp.block([[h, h], [h, -h]])
    return (h / math.sqrt(n)).astype(dtype)


def hadamard_ref(x: jnp.ndarray) -> jnp.ndarray:
    h = hadamard_matrix(x.shape[-1])
    return (x.astype(jnp.float32) @ h).astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantized flash-decode attention
# ---------------------------------------------------------------------------
def decode_attention_ref(
    q: jnp.ndarray,        # (B, Hkv, Gq, D) f32/bf16 — query heads grouped per kv head
    k_codes: jnp.ndarray,  # (B, Hkv, S, D) int8
    k_scale: jnp.ndarray,  # (B, Hkv, S, D/group) f32
    v_codes: jnp.ndarray,  # (B, Hkv, S, D) int8
    v_scale: jnp.ndarray,  # (B, Hkv, S, D/group) f32
    group: int,
    kv_len: Optional[jnp.ndarray] = None,  # scalar, or (B,) per-slot lengths
) -> jnp.ndarray:
    """Attention of one new token against a quantized KV cache.  A (B,)
    ``kv_len`` masks each batch row at its own slot length (the ragged
    slot-arena decode)."""
    b, hkv, gq, d = q.shape
    s = k_codes.shape[2]
    k = dequantize_ref(k_codes, k_scale, group)  # (B,Hkv,S,D)
    v = dequantize_ref(v_codes, v_scale, group)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32), k)
    scores = scores / math.sqrt(d)
    if kv_len is not None:
        lens = jnp.atleast_1d(jnp.asarray(kv_len))          # (1,) or (B,)
        mask = jnp.arange(s)[None, :] < lens[:, None]       # (B|1, S)
        scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", probs, v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged quantized decode attention (block-table gather + fused dequant)
# ---------------------------------------------------------------------------
def paged_verify_attention_ref(
    q: jnp.ndarray,             # (B, Hkv, W, Gq, D)
    k_codes: jnp.ndarray,       # (P, Hkv, PS, D) int8 or (P, Hkv, PS, D/2) u8
    k_scale: jnp.ndarray,       # (P, Hkv, PS, D/group) f32
    v_codes: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, PPS) int32 page ids; 0 = unmapped
    kv_lens: jnp.ndarray,       # (B,) int32; query 0's visible length
    bits: int,
    group: int,
) -> jnp.ndarray:
    """Oracle for kernels/paged_verify_attention.py: the speculative
    multi-token verify step.  Query ``j`` of slot ``b`` attends cache
    positions ``< kv_lens[b] + j`` — the staircase causal mask over the
    ``W`` consecutive verify positions (each new token's own scattered
    KV row included, its successors excluded)."""
    bt = jnp.asarray(block_tables, jnp.int32)
    b, hkv, w, gq, d = q.shape

    def gather(pool):
        g = jnp.take(pool, bt, axis=0)       # (B, PPS, Hkv, PS, X)
        g = jnp.moveaxis(g, 2, 1)            # (B, Hkv, PPS, PS, X)
        return g.reshape(g.shape[0], g.shape[1], -1, g.shape[-1])

    kc, ks = gather(k_codes), gather(k_scale)
    vc, vs = gather(v_codes), gather(v_scale)
    if bits == 4:
        kc, vc = unpack_int4_ref(kc), unpack_int4_ref(vc)
    k = dequantize_ref(kc, ks, group)        # (B, Hkv, S, D)
    v = dequantize_ref(vc, vs, group)
    s = k.shape[2]
    scores = jnp.einsum("bhwgd,bhsd->bhwgs", q.astype(jnp.float32), k)
    scores = scores / math.sqrt(d)
    lens = jnp.asarray(kv_lens, jnp.int32)   # (B,)
    limit = lens[:, None] + jnp.arange(w)[None, :]          # (B, W)
    mask = jnp.arange(s)[None, None, :] < limit[..., None]  # (B, W, S)
    scores = jnp.where(mask[:, None, :, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhwgs,bhsd->bhwgd", probs, v)
    return out.astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,             # (B, Hkv, Gq, D)
    k_codes: jnp.ndarray,       # (P, Hkv, PS, D) int8 or (P, Hkv, PS, D/2) u8
    k_scale: jnp.ndarray,       # (P, Hkv, PS, D/group) f32
    v_codes: jnp.ndarray,
    v_scale: jnp.ndarray,
    block_tables: jnp.ndarray,  # (B, PPS) int32 page ids; 0 = unmapped
    kv_lens: jnp.ndarray,       # (B,) int32 valid lengths
    bits: int,
    group: int,
) -> jnp.ndarray:
    """Oracle for kernels/paged_attention.py: materialize each slot's
    pages into a contiguous (B, Hkv, S, ·) view, then reuse the dense
    decode-attention oracle with per-slot masking."""
    bt = jnp.asarray(block_tables, jnp.int32)

    def gather(pool):
        g = jnp.take(pool, bt, axis=0)       # (B, PPS, Hkv, PS, X)
        g = jnp.moveaxis(g, 2, 1)            # (B, Hkv, PPS, PS, X)
        return g.reshape(g.shape[0], g.shape[1], -1, g.shape[-1])

    kc, ks = gather(k_codes), gather(k_scale)
    vc, vs = gather(v_codes), gather(v_scale)
    if bits == 4:
        kc, vc = unpack_int4_ref(kc), unpack_int4_ref(vc)
    return decode_attention_ref(q, kc, ks, vc, vs, group,
                                kv_len=jnp.asarray(kv_lens, jnp.int32))
