"""Pallas TPU kernel: blockwise Hadamard transform.

TPU adaptation note (DESIGN.md §3): on GPUs the fast Hadamard transform is
a butterfly over warp shuffles; the TPU has no lane-shuffle analogue, and
the MXU is a 128×128 systolic array that multiplies dense 128-wide tiles at
full rate — so the TPU-optimal Hadamard for head_dim ≤ 256 *is* a dense
matmul against the (constant) H matrix, fused over token tiles.  This kernel
keeps H resident in VMEM across the whole grid (constant operand), reading
each token tile once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant_pack import F32_DOT
from repro.kernels.ref import hadamard_matrix


def _hadamard_kernel(x_ref, h_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)   # (BT, D)
    h = h_ref[...].astype(jnp.float32)   # (D, D)
    o_ref[...] = jnp.dot(
        x, h, precision=F32_DOT,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def hadamard_transform(x: jnp.ndarray, block_tokens: int = 256,
                       interpret: bool = False) -> jnp.ndarray:
    """x (T, D) -> x @ H_D.  D must be a power of two (64/128/256)."""
    t, d = x.shape
    assert d & (d - 1) == 0, f"D={d} must be a power of two"
    bt = min(block_tokens, t)
    assert t % bt == 0
    h = hadamard_matrix(d)
    return pl.pallas_call(
        _hadamard_kernel,
        grid=(t // bt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((d, d), lambda i: (0, 0)),  # constant across grid
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        interpret=interpret,
    )(x, h)
