"""What every architecture's plain float32 reference shares.

The references themselves live one per architecture in ``bench/archs/``
(their contract is in ``bench/archs/__init__.py``).  They import nothing
of the program: weights are redrawn from the seed one layer at a time
(``bench.weights.draw_leaf``, the same bf16 values the program serves),
widened to float32, and every matrix product runs at ``HIGHEST``
precision (:func:`_mm`).

Serving hands a prompt's keys and values to decode through a compression
strategy.  Queries of the prompt itself (the prefill, which gives the first
token) attend to the exact keys and values; queries of the generated
positions attend to the prompt's keys and values after the strategy's
quantize-and-restore, written out in :func:`restore` from the strategy's
published definition (min/max group quantization with fp16 scale and zero
point, anchor deltas, layer tiers), plus their own exact ones
(:func:`handed_off`, one attention layer at a time).

The lower-precision control runs the same forward with fp8 (e4m3)
weights (:func:`to_fp8`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


@dataclass
class Served:
    """One finished request: its prompt, the tokens it was served, and the
    strategy its prompt's keys and values passed through on their way to
    decode (None: decode read the exact prefill cache)."""

    prompt: np.ndarray
    tokens: np.ndarray
    strategy: Optional[Dict] = None


# ---------------------------------------------------------------------------
# The KV hand-off: quantize and restore, per layer, on the host
# ---------------------------------------------------------------------------
def _groups(x: np.ndarray, grouping: str, size: int):
    """(H, S, D) -> grouped view and the reducing axis."""
    h, s, d = x.shape
    if grouping == "per_head":
        return x.reshape(h, 1, s * d), 2
    if grouping == "per_channel":          # token groups, stats per channel
        assert s % size == 0, (s, size)
        return x.reshape(h, s // size, size, d), 2
    if grouping == "per_token":            # channel groups, stats per token
        assert d % size == 0, (d, size)
        return x.reshape(h, s, d // size, size), 3
    raise ValueError(grouping)


def quantize_restore(x: np.ndarray, bits: int, grouping: str, size: int,
                     symmetric: bool) -> np.ndarray:
    """Min/max (or abs-max) group quantization to ``bits``, restored with
    the fp16 scale and zero point that the wire carries."""
    if bits >= 16:
        return x.astype(np.float16).astype(np.float32)
    g, axis = _groups(x.astype(np.float32), grouping, size)
    qmax = (1 << bits) - 1
    if symmetric:
        half = 1 << (bits - 1)
        scale = np.maximum(np.abs(g).max(axis=axis, keepdims=True)
                           / max(half - 1, 1), 1e-8)
        q = np.clip(np.rint(g / scale) + half, 0, qmax)
        out = (q - half) * scale.astype(np.float16).astype(np.float32)
    else:
        lo = g.min(axis=axis, keepdims=True)
        scale = np.maximum((g.max(axis=axis, keepdims=True) - lo) / qmax,
                           1e-8)
        q = np.clip(np.rint((g - lo) / scale), 0, qmax)
        out = (q * scale.astype(np.float16).astype(np.float32)
               + lo.astype(np.float16).astype(np.float32))
    return out.reshape(x.shape).astype(np.float32)


def _tier_bits(layer: int, n_layers: int, tier_bits, tier_fracs) -> int:
    n1 = max(int(round(n_layers * tier_fracs[0])), 1)
    n2 = max(int(round(n_layers * tier_fracs[1])), 1)
    if layer < n1:
        return int(tier_bits[0])
    if layer < n1 + n2:
        return int(tier_bits[1])
    return int(tier_bits[2])


def restore(x: np.ndarray, st: Dict, layer: int, n_layers: int,
            is_key: bool) -> np.ndarray:
    """One layer's prompt keys or values (H, S, D) after the strategy's
    compress and decompress.  Entropy codecs are lossless and leave the
    values as they are."""
    if (st["key_bits"] >= 16 and st["value_bits"] >= 16
            and st["codec"] == "none"):
        return x.astype(np.float16).astype(np.float32)   # identity: fp16
    if st["transform"] == "delta":
        s = x.shape[1]
        anchor = np.arange(s) // st["delta_group"] * st["delta_group"]
        base = x[:, anchor, :]
        y = x - base
    elif st["transform"] == "none":
        base, y = None, x
    else:
        raise NotImplementedError(f"transform {st['transform']}")
    q = st["quantizer"]
    if q == "uniform":
        bits = st["key_bits"] if is_key else st["value_bits"]
        grouping, sym = st["granularity"], st["symmetric"]
    elif q == "kivi":
        bits = st["key_bits"] if is_key else st["value_bits"]
        grouping = "per_channel" if is_key else "per_token"
        sym = False
    elif q == "cachegen":
        bits = _tier_bits(layer, n_layers, st["tier_bits"], st["tier_fracs"])
        grouping, sym = "per_channel", st["symmetric"]
    else:
        raise NotImplementedError(f"quantizer {q}")
    y = quantize_restore(y, bits, grouping, st["group_size"], sym)
    return y if base is None else (y + base).astype(np.float32)


# ---------------------------------------------------------------------------
# Lower precision for the control
# ---------------------------------------------------------------------------
def to_fp8(x, axis: int):
    """e4m3 with one scale per slice along ``axis`` (the reduced axis)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, w):
    return jnp.einsum(spec, a, w, precision=HI)


# ---------------------------------------------------------------------------
# Pieces of a forward
# ---------------------------------------------------------------------------
def _rms(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale)


def _rope(x, theta: float):
    """x (B, S, H, D) at positions 0..S-1, rotate-half pairing."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def handed_off(k, v, prompt: int, served, layer: int, n_layers: int):
    """Keys and values (B, S, KV, hd) of one attention layer as the
    generated positions read them: the prompt part through each request's
    strategy, the rest exact.  ``layer`` of ``n_layers`` picks the
    strategy's tier."""
    if all(r.strategy is None for r in served):
        return k, v
    kh = np.array(k[:, :prompt])               # (B, P, KV, hd)
    vh = np.array(v[:, :prompt])
    for b, r in enumerate(served):
        if r.strategy is None:
            continue
        for arr, is_key in ((kh, True), (vh, False)):
            x = arr[b].transpose(1, 0, 2)       # (KV, P, hd)
            arr[b] = restore(x, r.strategy, layer, n_layers,
                             is_key).transpose(1, 0, 2)
    k_dec = k.at[:, :prompt].set(jnp.asarray(kh))
    v_dec = v.at[:, :prompt].set(jnp.asarray(vh))
    return k_dec, v_dec
