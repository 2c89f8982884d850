"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig13,...] [--smoke]``
prints ``name,us_per_call,derived`` CSV rows.

``--all --smoke`` executes EVERY registered benchmark's smoke path
(CI-sized settings; each suite's deterministic asserts still run, so a
crash or a violated acceptance bound fails the harness).  ``--json PATH``
archives every emitted row for the CI artifact.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache


SUITES = [
    ("fig5_strategy_space", "benchmarks.strategy_space"),
    ("fig4_kv_latency_thresholds", "benchmarks.kv_latency_thresholds"),
    ("fig8_profiling_stability", "benchmarks.profiling_stability"),
    ("fig9_16l_bo_convergence", "benchmarks.bo_convergence"),
    ("fig10_pareto_frontier", "benchmarks.pareto_frontier"),
    ("tab1_acc_cr", "benchmarks.acc_cr_table"),
    ("fig13_jct_vs_bandwidth", "benchmarks.jct_vs_bandwidth"),
    ("fig14_ttft_prefix_caching", "benchmarks.ttft_prefix_caching"),
    ("fig15_latency_breakdown", "benchmarks.latency_breakdown"),
    ("fig16r_online_adaptivity", "benchmarks.online_adaptivity"),
    ("fig12_hardware_tiers", "benchmarks.hardware_tiers"),
    ("serving_continuous_batching", "benchmarks.continuous_batching"),
    ("serving_tiered_kv", "benchmarks.tiered_kv"),
    ("serving_cluster_scaling", "benchmarks.cluster_scaling"),
    ("serving_sim_speed", "benchmarks.sim_speed"),
    ("serving_trace_grid", "benchmarks.trace_grid"),
    ("serving_paged_arena", "benchmarks.paged_arena"),
    ("serving_speculative_decode", "benchmarks.speculative_decode"),
    ("kernels", "benchmarks.kernel_throughput"),
    ("roofline", "benchmarks.roofline"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of suite prefixes")
    ap.add_argument("--all", action="store_true",
                    help="run every registered suite (explicit form of the "
                         "default; combine with --smoke for the CI sweep)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized settings for every suite that supports "
                         "them; a crash or violated assert fails the run")
    ap.add_argument("--skip", default="",
                    help="comma list of suite prefixes to leave out (CI "
                         "uses this to avoid re-running suites already "
                         "executed as dedicated steps)")
    ap.add_argument("--json", default="",
                    help="archive all emitted rows to this JSON path")
    args = ap.parse_args(argv)
    if args.all and args.only:
        ap.error("--all and --only are mutually exclusive")
    enable_compile_cache()
    only = [s for s in args.only.split(",") if s]
    skip = [s for s in args.skip.split(",") if s]

    print("name,us_per_call,derived")
    failures = 0
    for name, module in SUITES:
        if only and not any(name.startswith(o) or o in name for o in only):
            continue
        if skip and any(name.startswith(s) or s in name for s in skip):
            print(f"# suite {name} skipped (--skip)")
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            kwargs = {}
            if args.smoke and \
                    "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            mod.run(**kwargs)
            print(f"# suite {name} done in {time.time()-t0:.1f}s")
        except Exception as e:
            failures += 1
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    if args.json:
        from benchmarks.common import write_json
        write_json(args.json)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
