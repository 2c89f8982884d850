"""Jamba-style hybrid (AI21-Jamba2-3B): its reference, counts and size check.

The architecture, from the configuration file's ``used`` (or
``rehearsal``) sizes and ``architecture`` block.  Layer ``i`` is attention
where ``i % attn_layer_period == attn_layer_offset``, else a Mamba-1
mixer; every layer has a dense MLP (``num_experts`` 1).  The program scans
one period of layers, so layer ``i``'s weights sit under
``['blocks']['layer{i % period}']`` at index ``i // period``.

    x = embed[tokens]
    per layer:  h = rms(x) * (1 + ln1)
      attention:  q, k, v = h Wq, h Wk, h Wv      (no positional encoding)
                  x += softmax(q k^T / sqrt(hd), causal) v Wo
      Mamba-1:    xi, z = h W_in
                  xc = silu(causal depthwise conv(xi) + conv_b)
                  dt, B, C = xc W_x;  dt, B, C = rms(dt), rms(B), rms(C)
                  dt = softplus(dt W_dt + b_dt);  A = -exp(A_log)
                  s_t = exp(dt_t A) * s_{t-1} + (dt_t xc_t) B_t   (sequential)
                  x += ((s_t . C_t) + D * xc) * silu(z) W_out
      h = rms(x) * (1 + ln2);  x += (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * (1 + final)) embed^T      (tied head)

Every norm is the program's ``x / rms(x) * (1 + scale)``: Jamba's
``weight * x / rms(x)`` with ``weight = 1 + scale``.

The hand-off: the prompt's keys and values of the attention layers pass
through the request's strategy for the generated positions (layer index
among the attention layers, as the program's ``KVCache`` holds them); the
SSM and conv state crosses exactly, so the reference carries it on as is.

Counts: a multiply-add counts two operations; attention is causal, a query
at position ``p`` attending to ``p + 1`` keys; the scan costs
``SCAN_OPS`` operations per state element per token.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from counts import BF16
from reference import HI, Served, _mm, _rms, handed_off, to_fp8
from weights import draw_leaf

F32 = 4
# exp(dt A) is a multiply and an exp; s * dA, dt x (per channel) times B,
# the add, and s . C's multiply and add
SCAN_OPS = 7


# ---------------------------------------------------------------------------
# The layout, from the file's keys
# ---------------------------------------------------------------------------
def _dims(m: Dict) -> Dict[str, int]:
    d = int(m["hidden_size"])
    h = int(m["num_attention_heads"])
    x = {"d": d, "h": h, "kv": int(m["num_key_value_heads"]),
         "hd": int(m.get("head_dim") or d // h),
         "ff": int(m["intermediate_size"]),
         "layers": int(m["num_hidden_layers"]),
         "vocab": int(m["vocab_size"]),
         "n": int(m["mamba_d_state"]), "conv": int(m["mamba_d_conv"]),
         "r": int(m["mamba_dt_rank"]),
         "di": int(m["mamba_expand"]) * d,
         "period": int(m["attn_layer_period"]),
         "offset": int(m["attn_layer_offset"])}
    x["attn"] = sum(is_attention(m, i) for i in range(x["layers"]))
    x["ssm"] = x["layers"] - x["attn"]
    return x


def is_attention(m: Dict, layer: int) -> bool:
    return layer % int(m["attn_layer_period"]) == int(m["attn_layer_offset"])


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
            "tie_word_embeddings": cfg.tie_embeddings,
            "rms_norm_eps": cfg.rmsnorm_eps,
            "attn_layer_period": cfg.attn_period,
            "attn_layer_offset": cfg.attn_offset,
            "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
            "mamba_expand": cfg.ssm_expand, "mamba_dt_rank": cfg.dt_rank,
            "num_experts": cfg.num_experts if cfg.moe else 1,
            # the program's mixer has a conv bias and no projection bias
            "mamba_conv_bias": True, "mamba_proj_bias": False}
    have_arch = {"ssm_norms": cfg.ssm_norms,
                 "positional_encoding": "rope" if cfg.use_rope else "none"}
    bad = {k: (v, used.get(k)) for k, v in have.items()
           if k in used and used[k] != v}
    if cfg.family != "hybrid" or not cfg.ssm:
        bad["family"] = (cfg.family, "hybrid")
    bad.update({k: (v, arch[k]) for k, v in have_arch.items()
                if arch[k] != v})
    return bad


def warm_handoff(cfg, prompt: int):
    """A random hand-off in the program's form: K and V of the attention
    layers, (layers, KV heads, prompt, head size), and the SSM layers'
    ``{"ssm": (layers, d_inner, d_state), "conv": (layers, d_inner,
    d_conv - 1)}``, float32 normals from a fixed seed."""
    specs = cfg.layer_specs()
    n_attn = sum(s.kind == "attn" for s in specs)
    n_ssm = len(specs) - n_attn
    rng = np.random.default_rng(0)
    shape = (n_attn, cfg.kv_heads, prompt, cfg.resolved_head_dim)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    state = {"ssm": rng.standard_normal(
                 (n_ssm, cfg.d_inner, cfg.ssm_state)).astype(np.float32),
             "conv": rng.standard_normal(
                 (n_ssm, cfg.d_inner, cfg.ssm_conv - 1)).astype(np.float32)}
    return k, v, state


# ---------------------------------------------------------------------------
# The reference forward, one layer at a time
# ---------------------------------------------------------------------------
def _mlp(eps, x, w):
    h = _rms(x, w["ln2"], eps)
    gate = _mm("bsd,df->bsf", h, w["wi_gate"])
    up = _mm("bsd,df->bsf", h, w["wi_up"])
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["mlp_wo"])


@partial(jax.jit, static_argnums=(0,))
def _mamba_layer(arch, x, w):
    """One Mamba-1 layer (mixer and MLP) over x (B, S, d), float32, with
    the recurrence run one token after another."""
    eps, norms = arch
    h = _rms(x, w["ln1"], eps)
    xz = _mm("bsd,de->bse", h, w["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)
    k = w["conv_w"].shape[1]
    pad = jnp.pad(xi, ((0, 0), (k - 1, 0), (0, 0)))
    s = xi.shape[1]
    xc = sum(pad[:, j:j + s] * w["conv_w"][:, j] for j in range(k))
    xc = jax.nn.silu(xc + w["conv_b"])
    n = w["a_log"].shape[1]
    r = w["dt_proj_w"].shape[0]
    dt, bm, cm = jnp.split(_mm("bse,ek->bsk", xc, w["x_proj"]),
                           [r, r + n], axis=-1)
    if norms:
        dt = _rms(dt, w["dt_norm"], eps)
        bm = _rms(bm, w["b_norm"], eps)
        cm = _rms(cm, w["c_norm"], eps)
    dt = jax.nn.softplus(_mm("bsr,re->bse", dt, w["dt_proj_w"])
                         + w["dt_proj_b"])
    a = -jnp.exp(w["a_log"])

    def step(st, xs):
        x_t, dt_t, b_t, c_t = xs                     # (B, di) x2, (B, n) x2
        st = (jnp.exp(dt_t[..., None] * a) * st
              + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return st, jnp.sum(st * c_t[:, None, :], axis=-1)

    st0 = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    _, y = jax.lax.scan(step, st0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (xc, dt, bm, cm)))
    y = (jnp.moveaxis(y, 0, 1) + xc * w["d_skip"]) * jax.nn.silu(z)
    return _mlp(eps, x + _mm("bse,ed->bsd", y, w["out_proj"]), w)


@partial(jax.jit, static_argnums=(0,))
def _qkv(eps, x, w):
    h = _rms(x, w["ln1"], eps)
    return (_mm("bsd,dhk->bshk", h, w["wq"]), _mm("bsd,dhk->bshk", h, w["wk"]),
            _mm("bsd,dhk->bshk", h, w["wv"]))


@partial(jax.jit, static_argnums=(0,))
def _attend(prompt: int, q, k, v, k_dec, v_dec):
    """One request's causal attention, (S, H, hd) queries over (S, KV, hd)
    keys: the prompt's queries over the exact keys and values, the
    generated ones over the handed-off prompt part plus their own."""
    s, hq, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, hq // kv, hd)

    def part(lo, hi, kk, vv):
        sc = jnp.einsum("qhgd,khd->hgqk", qg[lo:hi], kk[:hi],
                        precision=HI) / math.sqrt(hd)
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vv[:hi], precision=HI)

    out = jnp.concatenate([part(0, prompt, k, v),
                           part(prompt, s, k_dec, v_dec)])
    return out.reshape(s, hq, hd)


@partial(jax.jit, static_argnums=(0,))
def _out_and_mlp(eps, x, att, w):
    return _mlp(eps, x + _mm("bshk,hkd->bsd", att, w["wo"]), w)


@partial(jax.jit, static_argnums=(0,))
def _head(eps, x, final, unembed):
    return _mm("btd,dv->btv", _rms(x, final, eps), unembed)


Leaf = Callable[[str, Tuple[int, ...], int], jnp.ndarray]


class Reference:
    """The reference for one configuration file (its ``used`` or
    ``rehearsal`` sizes and its ``architecture`` block) and one weight
    seed.  ``leaf(path, shape, layer)``, where given, supplies the float32
    weights in place of the seed's draw (a test's own parameters)."""

    def __init__(self, model: Dict, arch: Dict, seed: int,
                 leaf: Optional[Leaf] = None):
        self.m, self.seed = model, seed
        self.x = _dims(model)
        self.tied = bool(model["tie_word_embeddings"])
        self.eps = float(model["rms_norm_eps"])
        self.norms = bool(arch["ssm_norms"])
        assert arch["positional_encoding"] == "none", arch
        self.leaf = leaf

    # -- weights ----------------------------------------------------------
    def _w(self, path: str, shape, layer: int = -1, fp8_axis=None):
        if self.leaf is not None:
            w = jnp.asarray(self.leaf(path, tuple(shape), layer), jnp.float32)
        else:
            w = draw_leaf(self.seed, path, tuple(shape),
                          layer).astype(jnp.float32)
        return w if fp8_axis is None else to_fp8(w, fp8_axis)

    def _layer_weights(self, i: int, fp8: bool) -> Dict:
        x = self.x
        d, ff, di, n, r = x["d"], x["ff"], x["di"], x["n"], x["r"]
        block = f"['blocks']['layer{i % x['period']}']"
        index = i // x["period"]

        def w(name, shape, axis):
            return self._w(block + name, shape, index, axis if fp8 else None)

        out = {"ln1": w("['ln1']['scale']", (d,), None),
               "ln2": w("['ln2']['scale']", (d,), None),
               "wi_gate": w("['mlp']['wi_gate']", (d, ff), 0),
               "wi_up": w("['mlp']['wi_up']", (d, ff), 0),
               "mlp_wo": w("['mlp']['wo']", (ff, d), 0)}
        if is_attention(self.m, i):
            h, kv, hd = x["h"], x["kv"], x["hd"]
            out.update(wq=w("['mixer']['wq']", (d, h, hd), 0),
                       wk=w("['mixer']['wk']", (d, kv, hd), 0),
                       wv=w("['mixer']['wv']", (d, kv, hd), 0),
                       wo=w("['mixer']['wo']", (h, hd, d), (0, 1)))
            return out
        out.update(
            in_proj=w("['mixer']['in_proj']", (d, 2 * di), 0),
            conv_w=w("['mixer']['conv_w']", (di, x["conv"]), 1),
            conv_b=w("['mixer']['conv_b']", (di,), None),
            x_proj=w("['mixer']['x_proj']", (di, r + 2 * n), 0),
            dt_proj_w=w("['mixer']['dt_proj_w']", (r, di), 0),
            dt_proj_b=w("['mixer']['dt_proj_b']", (di,), None),
            a_log=w("['mixer']['a_log']", (di, n), None),
            d_skip=w("['mixer']['d_skip']", (di,), None),
            out_proj=w("['mixer']['out_proj']", (di, d), 0))
        if self.norms:
            out.update(dt_norm=w("['mixer']['dt_norm']['scale']", (r,), None),
                       b_norm=w("['mixer']['b_norm']['scale']", (n,), None),
                       c_norm=w("['mixer']['c_norm']['scale']", (n,), None))
        return out

    # -- the check --------------------------------------------------------
    def logits(self, served: Sequence[Served], fp8: bool = False):
        """(B, T, V) float32 logits at each position that predicted a
        served token: the prompt's last, then each generated one."""
        prompt = len(served[0].prompt)
        n_out = len(served[0].tokens)
        assert all(len(r.prompt) == prompt and len(r.tokens) == n_out
                   for r in served), "one prompt and output length per call"
        ids = np.stack([np.concatenate([r.prompt, r.tokens[:-1]])
                        for r in served]).astype(np.int32)
        x = self.x
        tok = self._w("['embed']['tok']", (x["vocab"], x["d"]),
                      fp8_axis=1 if fp8 else None)
        h = jnp.take(tok, jnp.asarray(ids), axis=0)
        attn_index = 0
        for i in range(x["layers"]):
            w = self._layer_weights(i, fp8)
            if is_attention(self.m, i):
                q, k, v = _qkv(self.eps, h, w)
                k_dec, v_dec = handed_off(k, v, prompt, served, attn_index,
                                          x["attn"])
                att = jnp.stack([_attend(prompt, q[b], k[b], v[b], k_dec[b],
                                         v_dec[b])
                                 for b in range(len(served))])
                h = _out_and_mlp(self.eps, h, att, w)
                attn_index += 1
                del q, k, v, k_dec, v_dec, att
            else:
                h = _mamba_layer((self.eps, self.norms), h, w)
            del w
        final = self._w("['final_norm']['scale']", (x["d"],))
        if self.tied:
            unembed = tok.T
        else:
            unembed = self._w("['embed']['unembed']", (x["d"], x["vocab"]),
                              fp8_axis=0 if fp8 else None)
        del tok
        return _head(self.eps, h[:, prompt - 1:], final, unembed)

    def gaps(self, served: Sequence[Served], control: bool = False
             ) -> Tuple[List[np.ndarray], Optional[List[np.ndarray]]]:
        """Per request, one gap per served token: the reference's best
        logit minus the logit of the token served; and, with ``control``,
        minus that of the token the fp8 forward ranks first there."""
        logits = self.logits(served)
        tokens = jnp.asarray(
            np.stack([r.tokens for r in served]).astype(np.int32))
        best = logits.max(-1)
        mine = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
        gaps = list(np.asarray(best - mine))
        if not control:
            return gaps, None
        pick = jnp.argmax(self.logits(served, fp8=True), -1)
        theirs = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
        return gaps, list(np.asarray(best - theirs))


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes alone
# ---------------------------------------------------------------------------
def attention_params(m: Dict) -> int:
    x = _dims(m)
    return x["d"] * x["hd"] * (2 * x["h"] + 2 * x["kv"])


def mamba_matmul_params(m: Dict) -> int:
    """in_proj, x_proj, dt_proj and out_proj: the weights one token
    multiplies through in one Mamba mixer."""
    x = _dims(m)
    di = x["di"]
    return (x["d"] * 2 * di + di * (x["r"] + 2 * x["n"]) + x["r"] * di
            + di * x["d"])


def mamba_params(m: Dict) -> int:
    """Every parameter of one Mamba mixer: the projections, the conv's
    weight and bias, dt's bias, A, D and the dt/B/C norms."""
    x = _dims(m)
    di = x["di"]
    norms = x["r"] + 2 * x["n"] if m.get("ssm_norms") else 0
    return (mamba_matmul_params(m) + di * x["conv"] + di + di
            + di * x["n"] + di + norms)


def param_count(m: Dict) -> int:
    """Every parameter the program holds (norm scales included)."""
    x = _dims(m)
    per_layer = 3 * x["d"] * x["ff"] + 2 * x["d"]
    heads = 1 if m.get("tie_word_embeddings") else 2
    return (x["layers"] * per_layer + x["attn"] * attention_params(m)
            + x["ssm"] * mamba_params(m) + heads * x["vocab"] * x["d"]
            + x["d"])


def state_bytes(m: Dict) -> int:
    """One request's recurrent state: float32 scan state and bf16 conv
    state of every SSM layer."""
    x = _dims(m)
    return x["ssm"] * x["di"] * (x["n"] * F32 + (x["conv"] - 1) * BF16)


def kv_bytes_per_token(m: Dict) -> int:
    x = _dims(m)
    return 2 * x["attn"] * x["kv"] * x["hd"] * BF16


def handoff_bytes(m: Dict, prompt: int) -> int:
    """The attention layers' prompt keys and values in bf16, plus the
    state, whose size does not grow with the prompt."""
    return prompt * kv_bytes_per_token(m) + state_bytes(m)


def _token_flops(m: Dict) -> int:
    """One token through every layer, attention's keys left out: the
    matmuls, the conv, the scan and the head."""
    x = _dims(m)
    matmul = (x["layers"] * 3 * x["d"] * x["ff"]
              + x["attn"] * attention_params(m)
              + x["ssm"] * mamba_matmul_params(m))
    per_ssm = 2 * x["conv"] * x["di"] + SCAN_OPS * x["di"] * x["n"]
    return 2 * matmul + x["ssm"] * per_ssm


def attention_flops(m: Dict, n_keys: int) -> int:
    """QK^T and PV of one query over ``n_keys`` keys, all attention
    layers."""
    x = _dims(m)
    return 4 * x["attn"] * x["h"] * x["hd"] * n_keys


def prefill_flops(m: Dict, prompt: int) -> int:
    """One batch-1 prefill of ``prompt`` tokens; logits for the last
    position only (the program unembeds just that one)."""
    x = _dims(m)
    keys = prompt * (prompt + 1) // 2          # causal
    return (prompt * _token_flops(m) + attention_flops(m, keys)
            + 2 * x["d"] * x["vocab"])


def decode_flops(m: Dict, positions: Iterable[int]) -> int:
    """One decode step over live slots whose new token sits at each of
    ``positions`` (it attends to ``position + 1`` keys)."""
    x = _dims(m)
    per_token = _token_flops(m) + 2 * x["d"] * x["vocab"]
    return sum(per_token + attention_flops(m, p + 1) for p in positions)


def decode_bytes(m: Dict, positions: Iterable[int]) -> int:
    """Bytes one decode step needs: every weight once (the tied embedding
    once, as the unembedding), the live slots' cached keys and values and
    the new rows written, and each live slot's state read and written."""
    x = _dims(m)
    weights = param_count(m)
    if not m.get("tie_word_embeddings"):
        weights -= x["vocab"] * x["d"]          # gathered rows only
    kv = kv_bytes_per_token(m)
    return weights * BF16 + sum(p * kv + kv + 2 * state_bytes(m)
                                for p in positions)
