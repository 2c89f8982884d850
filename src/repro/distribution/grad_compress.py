"""Gradient compression with error feedback (beyond-paper extension).

The paper's conclusion points at generalising service-aware compression to
"parameter offloading" and other networked state movement; gradient sync is
the training-side analogue.  Two pieces:

1. ``make_grad_transform`` — quantize gradients (error-feedback corrected)
   before the optimizer; emulates the wire format of a compressed gradient
   exchange and bounds the induced error (tested).
2. ``make_cross_pod_grad_sync`` — a shard_map collective that exchanges
   *quantized* gradients across the ``pod`` axis (the cross-DCN hop that is
   bandwidth-starved in multi-pod training), keeping in-pod reductions in
   full precision.  Wire bytes drop by 16/bits on the pod link.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distribution.kv_transfer import (
    dequantize_sym,
    pack_int4,
    quantize_sym,
    unpack_int4,
)


def _quant_roundtrip(g: jnp.ndarray, bits: int, group: int) -> jnp.ndarray:
    if g.ndim == 0 or g.shape[-1] % min(group, max(g.shape[-1], 1)):
        return g
    gg = min(group, g.shape[-1])
    q, scale = quantize_sym(g, bits, gg)
    return dequantize_sym(q, scale, gg, dtype=jnp.float32)


def make_grad_transform(bits: int = 8, group: int = 64,
                        error_feedback: bool = True) -> Callable:
    """grad_transform(grads, opt_state) -> (grads_hat, opt_state).

    opt_state must carry an "ef" tree (zeros_like grads) when
    error_feedback=True — see ``init_ef_state``."""

    def transform(grads, opt_state):
        if error_feedback and "ef" in opt_state:
            corrected = jax.tree_util.tree_map(
                lambda g, e: g.astype(jnp.float32) + e, grads, opt_state["ef"])
        else:
            corrected = grads
        g_hat = jax.tree_util.tree_map(
            lambda g: _quant_roundtrip(g, bits, group), corrected)
        if error_feedback and "ef" in opt_state:
            new_ef = jax.tree_util.tree_map(
                lambda c, h: c - h.astype(jnp.float32), corrected, g_hat)
            opt_state = {**opt_state, "ef": new_ef}
        return g_hat, opt_state

    return transform


def init_ef_state(grads_like) -> Dict[str, Any]:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32)
        if not isinstance(x, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(x.shape, jnp.float32),
        grads_like)


def make_cross_pod_grad_sync(mesh: Mesh, grads_example, param_specs,
                             bits: int = 8, group: int = 64):
    """Average gradients across pods with quantized exchange.

    Each pod keeps its own grads in f32 and receives its peers' grads as
    int codes + f16 scales.  For npod pods the exchange runs a ring of
    npod-1 quantized hops."""
    npod = mesh.shape["pod"]
    assert npod >= 2

    def pod_specs(spec):
        # grads are sharded like params over (data, model); the pod axis is
        # pure DP (replicated grads per pod pre-sync).
        return spec

    specs = param_specs

    def body(grads):
        def sync_leaf(g):
            if g.ndim == 0:
                acc = g
                for k in range(1, npod):
                    perm = [(i, (i + k) % npod) for i in range(npod)]
                    acc = acc + jax.lax.ppermute(g, "pod", perm)
                return acc / npod
            gg = min(group, g.shape[-1])
            packable = g.shape[-1] % gg == 0 and gg % 2 == 0
            acc = g.astype(jnp.float32)
            for k in range(1, npod):
                perm = [(i, (i + k) % npod) for i in range(npod)]
                if not packable:
                    acc = acc + jax.lax.ppermute(g, "pod", perm).astype(jnp.float32)
                    continue
                q, scale = quantize_sym(g, bits, gg)
                if bits == 4:
                    q = pack_int4(q)
                q = jax.lax.ppermute(q, "pod", perm)
                scale = jax.lax.ppermute(scale, "pod", perm)
                if bits == 4:
                    q = unpack_int4(q)
                acc = acc + dequantize_sym(q, scale, gg, dtype=jnp.float32)
            return (acc / npod).astype(g.dtype)

        return jax.tree_util.tree_map(sync_leaf, grads)

    mapped = jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                           out_specs=specs, check_vma=False)
    return jax.jit(mapped)
