"""Pallas TPU kernel: fused symmetric group-quantize + bit-pack.

The KV-compression hot path on the prefill worker: read a bf16 KV tile from
HBM once, quantize per group in VMEM, and emit int8 codes (or nibble-packed
int4, half-split as in ``ref.pack_int4_ref``) plus fp16-representable
scales.  One pass — no intermediate bf16 round-trip to HBM (the GPU
implementations in the paper run quant and pack as separate kernels).

Tiling: rows are tokens (8·k sublanes), the channel dim D sits in lanes
(128-aligned for head_dim ∈ {64,128,256} after flattening heads).  Block
shape (BT, D): the working set BT*D*4B plus outputs stays well under VMEM
(BT=256, D=512 → ~1 MB).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Precision of every f32 matmul in the kernels.  Mosaic's default
# multiplies f32 operands in one bf16 pass, which on a TPU v5e put the
# attention and Hadamard kernels ~1e-2 off their f32 oracles.
F32_DOT = jax.lax.Precision.HIGHEST


def _lane_group(shape, group: int):
    """Group id of every lane of a (rows, D) tile."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // group


def group_absmax(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """(R, D) -> (R, D/group): max |x| of each channel group.

    Mosaic lowers no reshape that splits the lane dim, so each group is
    reduced under a lane mask instead of as a (R, D/group, group) view."""
    r, d = x.shape
    ax = jnp.abs(x)
    if d == group:
        return jnp.max(ax, axis=-1, keepdims=True)
    lane = _lane_group((r, d), group)
    return jnp.concatenate(
        [jnp.max(jnp.where(lane == i, ax, 0.0), axis=-1, keepdims=True)
         for i in range(d // group)], axis=-1)


def expand_groups(s: jnp.ndarray, d: int) -> jnp.ndarray:
    """(R, D/group) -> (R, D): each group's value on all of its lanes."""
    r, ng = s.shape
    full = jnp.broadcast_to(s[:, 0:1], (r, d))
    lane = _lane_group((r, d), d // ng)
    for i in range(1, ng):
        full = jnp.where(lane == i, s[:, i:i + 1], full)
    return full


def unpack_codes(c: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Stored codes tile -> signed int32 codes (R, D).  int4 is half-split
    (see ``ref.pack_int4_ref``); nibble ops run in int32, since Mosaic
    has no 8-bit vector shifts."""
    c = c.astype(jnp.int32)
    if bits == 4:
        return jnp.concatenate([(c & 0x0F) - 8, (c >> 4) - 8], axis=-1)
    return c


def dequant_tile(c: jnp.ndarray, s: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Codes (R, D') + group scales (R, D/group) -> f32 values (R, D)."""
    q = unpack_codes(c, bits)
    return q.astype(jnp.float32) * expand_groups(s.astype(jnp.float32),
                                                 q.shape[1])


def _quant_kernel(x_ref, codes_ref, scale_ref, *, bits: int, group: int):
    x = x_ref[...].astype(jnp.float32)  # (BT, D)
    d = x.shape[1]
    qmax = (1 << (bits - 1)) - 1
    scale = jnp.maximum(group_absmax(x, group) / qmax, 1e-8)  # (BT, D/group)
    q = jnp.clip(jnp.round(x / expand_groups(scale, d)), -qmax - 1, qmax)
    q = q.astype(jnp.int32)
    if bits == 4:
        # half-split nibbles (see ref.pack_int4_ref)
        u = q + 8
        codes_ref[...] = (u[:, :d // 2] | (u[:, d // 2:] << 4)).astype(jnp.uint8)
    else:
        codes_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def _dequant_kernel(codes_ref, scale_ref, out_ref, *, bits: int, out_dtype):
    out_ref[...] = dequant_tile(codes_ref[...], scale_ref[...],
                                bits).astype(out_dtype)


def quant_pack(x: jnp.ndarray, bits: int = 8, group: int = 64,
               block_tokens: int = 256, interpret: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (T, D) -> (codes (T, D*bits/8) int8/uint8, scales (T, D/group) f32).

    T need not divide ``block_tokens``: the tail block is zero-padded on
    the way in and sliced off the outputs (each token quantizes
    independently, so padding rows cannot perturb real ones).
    """
    t, d = x.shape
    assert d % group == 0 and bits in (4, 8)
    assert group % 2 == 0
    bt = min(block_tokens, t)
    pad = -t % bt
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)], axis=0)
    tp = t + pad
    cw = d if bits == 8 else d // 2
    cdtype = jnp.int8 if bits == 8 else jnp.uint8
    kernel = functools.partial(_quant_kernel, bits=bits, group=group)
    codes, scales = pl.pallas_call(
        kernel,
        grid=(tp // bt,),
        in_specs=[pl.BlockSpec((bt, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, cw), lambda i: (i, 0)),
            pl.BlockSpec((bt, d // group), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tp, cw), cdtype),
            jax.ShapeDtypeStruct((tp, d // group), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    if pad:
        codes, scales = codes[:t], scales[:t]
    return codes, scales


def dequant_unpack(codes: jnp.ndarray, scales: jnp.ndarray, bits: int = 8,
                   group: int = 64, block_tokens: int = 256,
                   out_dtype=jnp.bfloat16, interpret: bool = False
                   ) -> jnp.ndarray:
    t = codes.shape[0]
    d = codes.shape[1] * (2 if bits == 4 else 1)
    bt = min(block_tokens, t)
    pad = -t % bt
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros((pad,) + codes.shape[1:], codes.dtype)], axis=0)
        scales = jnp.concatenate(
            [scales, jnp.zeros((pad,) + scales.shape[1:], scales.dtype)],
            axis=0)
    tp = t + pad
    kernel = functools.partial(_dequant_kernel, bits=bits,
                               out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(tp // bt,),
        in_specs=[
            pl.BlockSpec((bt, codes.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((bt, d // group), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, d), out_dtype),
        interpret=interpret,
    )(codes, scales)
    return out[:t] if pad else out
