#!/usr/bin/env python3
"""Build the committed profile set of a configuration whose hand-off
carries recurrent state beside its attention layers' KV, once, offline.

    python3 bench/make_state_profiles.py --config jamba2-3b

As ``make_profiles.py`` (same strategy set, the program's offline
profiling, quality on the byte-level reference LM), but the sample is the
payload such a model hands over: random K and V of its attention layers
only (layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``) at the prompt length, plus a random float32 scan
state and conv state for each of its Mamba layers.  The strategies
compress the KV; the state crosses as it is, so the compression ratio and
the codec throughputs are those of the whole payload, as the controller
reads them.  Writes ``bench/profiles/<config>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from make_profiles import strategies  # noqa: E402


def sample(used, prompt: int):
    """The hand-off of one ``prompt``-token request of the ``used`` sizes."""
    from repro.core.kvcache import KVCache

    layers = used["num_hidden_layers"]
    n_attn = sum(i % used["attn_layer_period"] == used["attn_layer_offset"]
                 for i in range(layers))
    d_inner = used["mamba_expand"] * used["hidden_size"]
    hd = used.get("head_dim") or used["hidden_size"] // used[
        "num_attention_heads"]
    kv = KVCache.random(n_attn, used["num_key_value_heads"], prompt, hd,
                        seed=0)
    rng = np.random.default_rng(1)
    state = {"ssm": rng.standard_normal(
                 (layers - n_attn, d_inner, used["mamba_d_state"])),
             "conv": rng.standard_normal(
                 (layers - n_attn, d_inner, used["mamba_d_conv"] - 1))}
    return KVCache(kv.k, kv.v, state)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt-tokens", type=int, default=2048)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    os.environ.setdefault("REPRO_CACHE_DIR", str(ROOT / ".repro_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.profiles import save_profiles
    from repro.launch.profile_offline import build_profiles

    used = json.loads((BENCH / "configs" / f"{args.config}.json")
                      .read_text())["used"]
    kv = sample(used, args.prompt_tokens)
    profiles = build_profiles(strategies(), kv_samples=[kv],
                              quality_kwargs={"n_prompts": 4,
                                              "decode_tokens": 12},
                              verbose=True)
    out = args.out or str(BENCH / "profiles" / f"{args.config}.jsonl")
    save_profiles(profiles, out)
    print(json.dumps({"config": args.config, "out": out,
                      "payload_bytes": kv.nbytes_wire(),
                      "host": {"machine": platform.machine(),
                               "processor": platform.processor(),
                               "cpus": os.cpu_count(),
                               "python": platform.python_version()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
