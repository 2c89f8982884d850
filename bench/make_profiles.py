#!/usr/bin/env python3
"""Build a configuration's committed profile set, once, offline.

    python3 bench/make_profiles.py --config qwen3-4b

Runs the program's offline profiling (``build_profiles``) over the strategy
set below: compression ratio and host encode/decode throughput measured on
one random KV of the configuration's shape at the mixes' prompt length,
quality on the program's byte-level reference LM.  Writes
``bench/profiles/<config>.jsonl`` and prints the host it measured on.  The
benchmark's runs only read the file, so no run pays for profiling.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def strategies():
    """Strategy set: the paper's KIVI and CacheGen baselines, a packed
    int4 per-channel codec, and the two per-token symmetric layouts the
    paged arena can hold as quantized pages.  Each has its own profile
    name, which is how the check maps a served request to its strategy."""
    from repro.core.strategy import BASELINES, StrategyConfig

    return [BASELINES["kivi"], BASELINES["cachegen"],
            StrategyConfig(quantizer="uniform", key_bits=4, value_bits=4,
                           granularity="per_channel", codec="zstd3"),
            StrategyConfig(quantizer="uniform", key_bits=8, value_bits=8,
                           granularity="per_token", group_size=128,
                           symmetric=True),
            StrategyConfig(quantizer="uniform", key_bits=4, value_bits=4,
                           granularity="per_token", group_size=32,
                           symmetric=True)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt-tokens", type=int, default=512)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    os.environ.setdefault("REPRO_CACHE_DIR", str(ROOT / ".repro_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.kvcache import KVCache
    from repro.core.profiles import save_profiles
    from repro.launch.profile_offline import build_profiles

    used = json.loads((BENCH / "configs" / f"{args.config}.json")
                      .read_text())["used"]
    hd = used.get("head_dim") or used["hidden_size"] // used[
        "num_attention_heads"]
    kv = KVCache.random(used["num_hidden_layers"],
                        used["num_key_value_heads"], args.prompt_tokens, hd,
                        seed=0)
    profiles = build_profiles(strategies(), kv_samples=[kv],
                              quality_kwargs={"n_prompts": 4,
                                              "decode_tokens": 12},
                              verbose=True)
    out = args.out or str(BENCH / "profiles" / f"{args.config}.jsonl")
    save_profiles(profiles, out)
    print(json.dumps({"config": args.config, "out": out,
                      "host": {"machine": platform.machine(),
                               "processor": platform.processor(),
                               "cpus": os.cpu_count(),
                               "python": platform.python_version()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
