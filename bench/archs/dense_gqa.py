"""Dense GQA transformer: its reference, counts and size check.

The architecture, from the configuration file's ``used`` and
``architecture`` blocks, every layer alike and scanned with period 1 (its
weights under ``['blocks']['layer0']``, one layer per index):

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + ln1)
                q, k, v = h Wq, h Wk, h Wv;  q, k = rms(q) * (1 + qn), ...
                q, k = rope(q), rope(k)        (rotate-half, base theta)
                x += softmax(q k^T / sqrt(hd), causal) v Wo
                h = rms(x) * (1 + ln2);  x += (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * (1 + final)) Wu     (Wu = embed^T when tied)

Counts: a multiply-add counts two operations; attention is causal, a query
at position ``p`` attending to ``p + 1`` keys.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from counts import BF16
from reference import HI, Served, _mm, _rms, _rope, handed_off, to_fp8
from weights import draw_leaf

BLOCK = "['blocks']['layer0']"


# ---------------------------------------------------------------------------
# The size check
# ---------------------------------------------------------------------------
def check(cfg, used: Dict, arch: Dict) -> Dict:
    """The program's registered sizes against the configuration file's."""
    have = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.kv_heads,
            "head_dim": cfg.resolved_head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "tie_word_embeddings": cfg.tie_embeddings}
    have_arch = {"rmsnorm_eps": cfg.rmsnorm_eps, "rope_theta": cfg.rope_theta,
                 "qk_norm": cfg.qk_norm}
    bad = {k: (v, used.get(k)) for k, v in have.items()
           if k in used and used[k] != v}
    bad.update({k: (v, arch[k]) for k, v in have_arch.items()
                if arch[k] != v})
    return bad


def warm_handoff(cfg, prompt: int) -> Tuple[np.ndarray, np.ndarray]:
    """A random K and V of every layer, (layers, KV heads, prompt, head
    size) in float32."""
    shape = (cfg.num_layers, cfg.kv_heads, prompt, cfg.resolved_head_dim)
    rng = np.random.default_rng(0)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# The reference forward, one layer at a time
# ---------------------------------------------------------------------------
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
            k_dec, v_dec = handed_off(k, v, prompt, served, layer,
                                      self.layers)
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


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes alone
# ---------------------------------------------------------------------------
def _dims(m: Dict) -> Dict[str, int]:
    d = int(m["hidden_size"])
    h = int(m["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(m["num_key_value_heads"]),
            "hd": int(m.get("head_dim") or d // h),
            "ff": int(m["intermediate_size"]),
            "layers": int(m["num_hidden_layers"]),
            "vocab": int(m["vocab_size"])}


def matmul_params_per_layer(m: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    x = _dims(m)
    attn = x["d"] * x["hd"] * (2 * x["h"] + 2 * x["kv"])
    return attn + 3 * x["d"] * x["ff"]


def param_count(m: Dict) -> int:
    """Every parameter the program holds (norm scales included)."""
    x = _dims(m)
    per_layer = matmul_params_per_layer(m) + 2 * x["d"]
    if m.get("qk_norm"):
        per_layer += 2 * x["hd"]
    heads = 1 if m.get("tie_word_embeddings") else 2
    return x["layers"] * per_layer + heads * x["vocab"] * x["d"] + x["d"]


def kv_bytes_per_token(m: Dict) -> int:
    x = _dims(m)
    return 2 * x["layers"] * x["kv"] * x["hd"] * BF16


def handoff_bytes(m: Dict, prompt: int) -> int:
    """The prompt's keys and values in bf16: no state beside them."""
    return prompt * kv_bytes_per_token(m)


def attention_flops(m: Dict, n_keys: int) -> int:
    """QK^T and PV of one query over ``n_keys`` keys, all layers."""
    x = _dims(m)
    return 4 * x["layers"] * x["h"] * x["hd"] * n_keys


def prefill_flops(m: Dict, prompt: int) -> int:
    """One batch-1 prefill of ``prompt`` tokens; logits for the last
    position only (the program unembeds just that one)."""
    x = _dims(m)
    dense = 2 * prompt * x["layers"] * matmul_params_per_layer(m)
    keys = prompt * (prompt + 1) // 2          # causal
    return dense + attention_flops(m, keys) + 2 * x["d"] * x["vocab"]


def decode_flops(m: Dict, positions: Iterable[int]) -> int:
    """One decode step over live slots whose new token sits at each of
    ``positions`` (it attends to ``position + 1`` keys)."""
    x = _dims(m)
    per_token = (2 * x["layers"] * matmul_params_per_layer(m)
                 + 2 * x["d"] * x["vocab"])
    return sum(per_token + attention_flops(m, p + 1) for p in positions)


def decode_bytes(m: Dict, positions: Iterable[int]) -> int:
    """Bytes one decode step needs: every weight once (the embedding
    table only as the unembedding it doubles as, when tied), the live
    slots' cached keys and values, and the new rows written."""
    x = _dims(m)
    weights = param_count(m)
    if not m.get("tie_word_embeddings"):
        weights -= x["vocab"] * x["d"]          # gathered rows only
    kv = kv_bytes_per_token(m)
    return weights * BF16 + sum(p * kv + kv for p in positions)
