"""The unified KV-cache compression pipeline: ``BS = C(Q(T(X)))`` (Sec. 5.1).

``compress`` produces a :class:`CompressedKV` whose *payload is real bytes*
(bit-packed, entropy-coded); ``decompress`` round-trips through those bytes.
Structural metadata (scales, zero-points, transform anchors, indices) is kept
native but exactly byte-accounted, so the reported CR equals
``wire_bytes(original) / wire_bytes(compressed)`` including all metadata —
this reproduces e.g. KIVI's metadata-bounded CR ceiling (paper Sec. 7.3).

Stage implementations and the TPU/host split are described in DESIGN.md
§2-§3; :class:`CompressedKV` is also the payload the serving layer's
prefix-KV pool stores (DESIGN.md §9).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Tuple, Union

import numpy as np

from repro.core import codecs
from repro.core.kvcache import KVCache, state_nbytes
from repro.core.quantizers import (
    QuantBucket,
    QuantizedTensor,
    head_importance_scores,
    quantize_tensor,
)
from repro.core.strategy import SOURCE_BYTES, StrategyConfig, is_identity
from repro.core.transforms import apply_transform, invert_transform, transform_meta_bytes

if TYPE_CHECKING:
    from repro.core.quality import DeviceKV

HEADER_BYTES = 64  # fixed per-message framing overhead


@dataclass
class _BucketWire:
    """Wire form of one quant bucket: payload bytes + structural metadata."""

    payload: bytes
    bits: int
    grouping: str
    group_size: int
    symmetric: bool
    codes_shape: Tuple[int, ...]
    lh_index: np.ndarray
    scale: Optional[np.ndarray]
    zp: Optional[np.ndarray]
    token_index: Optional[np.ndarray]

    def meta_bytes(self) -> int:
        b = self.lh_index.size * 2
        if self.scale is not None:
            b += self.scale.size * 2
        if self.zp is not None:
            b += self.zp.size * 2
        if self.token_index is not None:
            b += self.token_index.size * 4
        return int(b)


@dataclass
class CompressedKV:
    strategy: StrategyConfig
    shape: Tuple[int, int, int, int]
    k_buckets: List[_BucketWire]
    v_buckets: List[_BucketWire]
    k_ctx: Dict[str, Any]
    v_ctx: Dict[str, Any]
    identity_payload: Optional[bytes] = None  # bypass path
    # The SSM layers' recurrent state (KVCache.state): no strategy touches
    # it; it crosses as held, exact.
    state: Optional[Dict[str, np.ndarray]] = None

    # ------------------------------------------------------------------
    def payload_bytes(self) -> int:
        if self.identity_payload is not None:
            kv = len(self.identity_payload)
        else:
            kv = sum(len(b.payload) for b in self.k_buckets + self.v_buckets)
        return kv + state_nbytes(self.state)

    def meta_bytes(self) -> int:
        if self.identity_payload is not None:
            return HEADER_BYTES
        m = sum(b.meta_bytes() for b in self.k_buckets + self.v_buckets)
        m += transform_meta_bytes(self.k_ctx) + transform_meta_bytes(self.v_ctx)
        return m + HEADER_BYTES

    def total_bytes(self) -> int:
        return self.payload_bytes() + self.meta_bytes()

    def original_bytes(self) -> int:
        return (int(np.prod(self.shape)) * 2 * SOURCE_BYTES
                + state_nbytes(self.state))

    def compression_ratio(self) -> float:
        return self.original_bytes() / max(self.total_bytes(), 1)


# ---------------------------------------------------------------------------
def _encode_quantized(qt: QuantizedTensor, codec: str) -> List[_BucketWire]:
    out = []
    for b in qt.buckets:
        if b.bits >= 16:
            payload = codecs.encode_f16(b.codes, codec)
        else:
            payload = codecs.encode_codes(b.codes, b.bits, codec)
        out.append(
            _BucketWire(
                payload=payload, bits=b.bits, grouping=b.grouping,
                group_size=b.group_size, symmetric=b.symmetric,
                codes_shape=tuple(b.codes.shape), lh_index=b.lh_index,
                scale=b.scale, zp=b.zp, token_index=b.token_index,
            )
        )
    return out


def _decode_quantized(wires: List[_BucketWire], shape, codec: str) -> QuantizedTensor:
    qt = QuantizedTensor(shape=shape)
    for w in wires:
        count = int(np.prod(w.codes_shape))
        if w.bits >= 16:
            codes = codecs.decode_f16(w.payload, count, codec).reshape(w.codes_shape)
        else:
            codes = codecs.decode_codes(w.payload, w.bits, count, codec).reshape(
                w.codes_shape
            )
        qt.buckets.append(
            QuantBucket(
                lh_index=w.lh_index, bits=w.bits, grouping=w.grouping,
                group_size=w.group_size, symmetric=w.symmetric, codes=codes,
                scale=w.scale, zp=w.zp, token_index=w.token_index,
            )
        )
    return qt


class CompressionPipeline:
    """Stateless compressor for one :class:`StrategyConfig`."""

    def __init__(self, strategy: StrategyConfig,
                 head_scores: Optional[np.ndarray] = None):
        strategy.validate()
        self.strategy = strategy
        self.head_scores = head_scores

    # ------------------------------------------------------------------
    def compress(self, kv: Union[KVCache, "DeviceKV"]) -> CompressedKV:
        """``kv`` is a host :class:`KVCache`, or a prompt's KV still on the
        device (:class:`repro.core.quality.DeviceKV`): the identity payload
        is then cast to fp16 on the device and pulled as is, and every
        other strategy runs on the float32 KV pulled to the host.  The
        strategy compresses K and V; a recurrent state rides along as it
        is."""
        cfg = self.strategy
        if is_identity(cfg):
            if isinstance(kv, KVCache):
                payload = np.concatenate(
                    [kv.k.ravel(), kv.v.ravel()]
                ).astype(np.float16).tobytes()
            else:
                payload = kv.fp16().tobytes()
            return CompressedKV(cfg, kv.shape, [], [], {"kind": "none"},
                                {"kind": "none"}, identity_payload=payload,
                                state=kv.state)
        if not isinstance(kv, KVCache):
            kv = kv.host()

        k_t, k_ctx = apply_transform(cfg.transform, kv.k, cfg.delta_group)
        v_t, v_ctx = apply_transform(cfg.transform, kv.v, cfg.delta_group)

        scores = self.head_scores
        if scores is None and cfg.quantizer in ("mixhq", "duo"):
            scores = head_importance_scores(kv.k)

        k_q = quantize_tensor(k_t, cfg, is_key=True, head_scores=scores)
        v_q = quantize_tensor(v_t, cfg, is_key=False, head_scores=scores)

        return CompressedKV(
            strategy=cfg, shape=kv.shape,
            k_buckets=_encode_quantized(k_q, cfg.codec),
            v_buckets=_encode_quantized(v_q, cfg.codec),
            k_ctx=k_ctx, v_ctx=v_ctx, state=kv.state,
        )

    # ------------------------------------------------------------------
    def decompress(self, comp: CompressedKV, fp16: bool = False) -> KVCache:
        """The float32 :class:`KVCache` of ``comp``.  With ``fp16``, an
        identity payload's K and V come back as the fp16 halves of one
        zero-copy view of its bytes, for a consumer that casts on the
        device (:func:`repro.core.quality.inject_kv`); every other
        strategy restores float32 either way."""
        cfg = comp.strategy
        if comp.identity_payload is not None:
            n = int(np.prod(comp.shape))
            flat = np.frombuffer(comp.identity_payload, dtype=np.float16,
                                 count=2 * n)
            if not fp16:
                flat = flat.astype(np.float32)
            kv = flat.reshape((2,) + tuple(comp.shape))
            return KVCache(kv[0], kv[1], comp.state)

        # The quantizer operated on *transformed* tensors whose channel dim
        # may have been padded (hadamard); recover that shape.
        k_shape = self._transformed_shape(comp.shape, comp.k_ctx)
        v_shape = self._transformed_shape(comp.shape, comp.v_ctx)
        k_q = _decode_quantized(comp.k_buckets, k_shape, cfg.codec)
        v_q = _decode_quantized(comp.v_buckets, v_shape, cfg.codec)
        k_t = k_q.dequantize()
        v_t = v_q.dequantize()
        k = invert_transform(k_t, comp.k_ctx)
        v = invert_transform(v_t, comp.v_ctx)
        return KVCache(k, v, comp.state)

    @staticmethod
    def _transformed_shape(shape, ctx) -> Tuple[int, int, int, int]:
        if ctx.get("kind") == "hadamard":
            return shape[:3] + (ctx["pad_dim"],)
        return tuple(shape)

    # ------------------------------------------------------------------
    def roundtrip(self, kv: KVCache) -> Tuple[KVCache, CompressedKV, float, float]:
        """(restored, compressed, enc_seconds, dec_seconds)."""
        t0 = time.perf_counter()
        comp = self.compress(kv)
        t1 = time.perf_counter()
        restored = self.decompress(comp)
        t2 = time.perf_counter()
        return restored, comp, t1 - t0, t2 - t1
