"""Operations and bytes of the served model's programs, from shapes alone.

A multiply-add counts two operations.  Attention is causal: a query at
position ``p`` attends to ``p + 1`` keys.  The dictionaries passed in are
the ``used`` block of a configuration file (Hugging Face key names).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

BF16 = 2
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak_for(kind: str) -> Dict:
    """The chip's published peaks (``peaks.json``) for a JAX
    ``device_kind``; a kind that is not in the table is an error."""
    table = json.loads(PEAKS.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


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
