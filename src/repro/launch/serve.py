"""Serving launcher: drives the real-execution disaggregated engine with
the Service-Aware Controller over a bandwidth trace.

``python -m repro.launch.serve --requests 12 --bandwidth-gbps 1``
"""
from __future__ import annotations

import argparse
import sys
from typing import List

import numpy as np

from repro.controller import ServiceAwareController
from repro.core.profiles import load_profiles
from repro.data.synthetic import WORKLOADS
from repro.serving.engine import DisaggregatedEngine
from repro.serving.network import GBPS, BandwidthTrace
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profiles", default="",
                    help="profiles.jsonl from profile_offline (else built-in)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--bandwidth-gbps", type=float, default=1.0)
    ap.add_argument("--slo", type=float, default=0.0)
    ap.add_argument("--q-min", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.profiles:
        profiles = load_profiles(args.profiles)
    else:
        from repro.launch.profile_offline import build_profiles
        from repro.core.strategy import BASELINES
        profiles = build_profiles(list(BASELINES.values()),
                                  quality_kwargs={"n_prompts": 4,
                                                  "decode_tokens": 12})

    controller = ServiceAwareController(
        {w: profiles for w in WORKLOADS})
    engine = DisaggregatedEngine(controller=controller)
    trace = BandwidthTrace.constant(args.bandwidth_gbps * GBPS)

    rng = np.random.default_rng(args.seed)
    names = list(WORKLOADS)
    print(f"{'workload':10s} {'profile':40s} {'jct':>8s} {'comm':>8s} "
          f"{'agree':>6s} {'wire':>10s}")
    for i in range(args.requests):
        w = names[int(rng.integers(0, len(names)))]
        res = engine.serve(w, trace, t_slo=args.slo, q_min=args.q_min,
                           seed=args.seed * 1000 + i)
        print(f"{w:10s} {res.profile:40s} {res.jct:8.3f} {res.t_comm:8.3f} "
              f"{res.agreement:6.3f} {res.wire_bytes:10d}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
