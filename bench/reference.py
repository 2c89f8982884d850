"""Plain float32 reference of the served model and of the KV hand-off.

It imports nothing of the program.  Weights are redrawn from the seed one
layer at a time (``bench.weights.draw_leaf``, the same bf16 values the
program serves), widened to float32, and every matrix product runs at
``HIGHEST`` precision.  The architecture is written out here from the
configuration file's ``used`` and ``architecture`` blocks:

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + ln1)
                q, k, v = h Wq, h Wk, h Wv;  q, k = rms(q) * (1 + qn), ...
                q, k = rope(q), rope(k)        (rotate-half, base theta)
                x += softmax(q k^T / sqrt(hd), causal) v Wo
                h = rms(x) * (1 + ln2);  x += (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * (1 + final)) Wu     (Wu = embed^T when tied)

Serving hands a prompt's keys and values to decode through a compression
strategy.  Queries of the prompt itself (the prefill, which gives the first
token) attend to the exact keys and values; queries of the generated
positions attend to the prompt's keys and values after the strategy's
quantize-and-restore, written out in :func:`restore` from the strategy's
published definition (min/max group quantization with fp16 scale and zero
point, anchor deltas, layer tiers), plus their own exact ones.

``gaps`` returns, for every served token, how far its reference logit lies
below the reference's best.  With ``control`` set, the same forward also
runs with fp8 (e4m3) weights, and the gap is read as well for the token
that this lower precision ranks first at each position.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from weights import draw_leaf

HI = jax.lax.Precision.HIGHEST
BLOCK = "['blocks']['layer0']"
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
# The forward, one layer at a time
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


@partial(jax.jit, static_argnums=(0,))
def _qkv(arch, x, w):
    eps, theta, qk_norm = arch
    h = _rms(x, w["ln1"], eps)
    q = _mm("bsd,dhk->bshk", h, w["wq"])
    k = _mm("bsd,dhk->bshk", h, w["wk"])
    v = _mm("bsd,dhk->bshk", h, w["wv"])
    if qk_norm:
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
    return _rope(q, theta), _rope(k, theta), v


@partial(jax.jit, static_argnums=(0, 1))
def _finish_layer(arch, prompt: int, x, q, k, v, k_dec, v_dec, w):
    """Attention (prompt queries over exact keys, generated queries over
    the handed-off prompt keys plus their own), output projection, MLP."""
    eps = arch[0]
    b, s, hq, hd = q.shape
    g = hq // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], g, hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def attend(kk, vv):
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kk,
                        precision=HI) / math.sqrt(hd)
        sc = jnp.where(causal[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, vv, precision=HI)

    is_dec = (jnp.arange(s) >= prompt)[None, :, None, None, None]
    out = jnp.where(is_dec, attend(k_dec, v_dec), attend(k, v))
    x = x + _mm("bshk,hkd->bsd", out.reshape(b, s, hq, hd), w["wo"])
    h = _rms(x, w["ln2"], eps)
    gate = _mm("bsd,df->bsf", h, w["wi_gate"])
    up = _mm("bsd,df->bsf", h, w["wi_up"])
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["mlp_wo"])


@partial(jax.jit, static_argnums=(0,))
def _head(arch, x, final, unembed, tokens):
    """Logits at the positions that predicted each served token; returns
    (best logit, logit of the served token, argmax, logits)."""
    logits = _mm("btd,dv->btv", _rms(x, final, arch[0]), unembed)
    best = logits.max(-1)
    mine = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return best, mine, jnp.argmax(logits, -1), logits


class Reference:
    """The reference for one configuration file (its ``used`` sizes and
    ``architecture`` block) and one weight seed."""

    def __init__(self, model: Dict, arch: Dict, seed: int):
        self.m, self.seed = model, seed
        self.d = int(model["hidden_size"])
        self.h = int(model["num_attention_heads"])
        self.kv = int(model["num_key_value_heads"])
        self.hd = int(model.get("head_dim") or self.d // self.h)
        self.ff = int(model["intermediate_size"])
        self.layers = int(model["num_hidden_layers"])
        self.vocab = int(model["vocab_size"])
        self.tied = bool(model["tie_word_embeddings"])
        self.eps = float(arch["rmsnorm_eps"])
        self.theta = float(arch["rope_theta"])
        self.qk_norm = bool(arch["qk_norm"])

    # -- weights ----------------------------------------------------------
    def _w(self, path: str, shape, layer: int = -1, fp8_axis=None):
        w = draw_leaf(self.seed, path, tuple(shape), layer).astype(jnp.float32)
        return w if fp8_axis is None else to_fp8(w, fp8_axis)

    def _layer_weights(self, layer: int, fp8: bool):
        d, h, kv, hd, ff = self.d, self.h, self.kv, self.hd, self.ff

        def w(name, shape, axis):
            return self._w(BLOCK + name, shape, layer, axis if fp8 else None)

        out = {"ln1": w("['ln1']['scale']", (d,), None),
               "ln2": w("['ln2']['scale']", (d,), None),
               "wq": w("['mixer']['wq']", (d, h, hd), 0),
               "wk": w("['mixer']['wk']", (d, kv, hd), 0),
               "wv": w("['mixer']['wv']", (d, kv, hd), 0),
               "wo": w("['mixer']['wo']", (h, hd, d), (0, 1)),
               "wi_gate": w("['mlp']['wi_gate']", (d, ff), 0),
               "wi_up": w("['mlp']['wi_up']", (d, ff), 0),
               "mlp_wo": w("['mlp']['wo']", (ff, d), 0)}
        if self.qk_norm:
            out["q_norm"] = w("['mixer']['q_norm']", (hd,), None)
            out["k_norm"] = w("['mixer']['k_norm']", (hd,), None)
        return out

    # -- the check --------------------------------------------------------
    def gaps(self, served: Sequence[Served], control: bool = False
             ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
        """Per request, one gap per served token: the reference's best
        logit minus the logit of the token served; and, with ``control``,
        minus that of the token the fp8 forward ranks first there."""
        prompt = len(served[0].prompt)
        n_out = len(served[0].tokens)
        assert all(len(r.prompt) == prompt and len(r.tokens) == n_out
                   for r in served), "one prompt and output length per call"
        ids = np.stack([np.concatenate([r.prompt, r.tokens[:-1]])
                        for r in served]).astype(np.int32)
        best, mine, _, logits = self._forward(ids, prompt, served, False)
        gaps = list(np.asarray(best - mine))
        if not control:
            return gaps, None
        pick = self._forward(ids, prompt, served, True)[2]
        theirs = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
        return gaps, list(np.asarray(best - theirs))

    def _forward(self, ids: np.ndarray, prompt: int, served, fp8: bool):
        arch = (self.eps, self.theta, self.qk_norm)
        tok = self._w("['embed']['tok']", (self.vocab, self.d),
                      fp8_axis=1 if fp8 else None)
        x = jnp.take(tok, jnp.asarray(ids), axis=0)
        for layer in range(self.layers):
            w = self._layer_weights(layer, fp8)
            q, k, v = _qkv(arch, x, w)
            k_dec, v_dec = self._handed_off(k, v, prompt, served, layer)
            x = _finish_layer(arch, prompt, x, q, k, v, k_dec, v_dec, w)
            del w, q, k, v, k_dec, v_dec
        final = self._w("['final_norm']['scale']", (self.d,))
        if self.tied:
            unembed = tok.T
        else:
            unembed = self._w("['embed']['unembed']", (self.d, self.vocab),
                              fp8_axis=0 if fp8 else None)
        del tok
        tokens = jnp.asarray(
            np.stack([r.tokens for r in served]).astype(np.int32))
        return _head(arch, x[:, prompt - 1:], final, unembed, tokens)

    def _handed_off(self, k, v, prompt: int, served, layer: int):
        """Keys and values the generated positions read: the prompt part
        through each request's strategy, the rest exact."""
        if all(r.strategy is None for r in served):
            return k, v
        kh = np.array(k[:, :prompt])               # (B, P, KV, hd)
        vh = np.array(v[:, :prompt])
        for b, r in enumerate(served):
            if r.strategy is None:
                continue
            for arr, is_key in ((kh, True), (vh, False)):
                x = arr[b].transpose(1, 0, 2)       # (KV, P, hd)
                arr[b] = restore(x, r.strategy, layer, self.layers,
                                 is_key).transpose(1, 0, 2)
        k_dec = k.at[:, :prompt].set(jnp.asarray(kh))
        v_dec = v.at[:, :prompt].set(jnp.asarray(vh))
        return k_dec, v_dec
