"""Continuous-batching serving runtime (DESIGN.md §9, EXPERIMENTS.md
§Serving): offered load × SLO mix × store capacity, plus the slot-arena
decode scaling sweep.

Part A drives the *real-execution* ServingRuntime (tiny model, real
compressed bytes, modelled loaded-cluster compute) and checks the two
acceptance properties: ≥4 concurrent in-flight requests, and prefix-pool
hits beating cold prefill on TTFT.

Part B is the slots-vs-step-time sweep: per-iteration decode wall-clock
of the batched slot arena (ONE jitted call for all slots) against the
PR-1 per-slot loop (one batch-1 call + host round-trip per slot), with a
token-exact parity check between the two paths.  The arena must stay
within 2× of its 1-slot step time at 8 slots; the loop degrades ~N×.

Part C sweeps the event-driven simulator through the same shared
scheduler/store code path at scale.

CLI: ``--smoke`` shrinks everything to CI-sized settings (and skips the
hard scaling assertion — timing on shared CI runners is advisory);
``--json PATH`` archives the emitted rows as JSON.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.common import emit, write_json
from repro.core.profiles import Profile
from repro.core.strategy import StrategyConfig
from repro.serving import (
    GBPS,
    BandwidthTrace,
    NoCompressionPolicy,
    PrefixKVStore,
    SchedulerConfig,
    SimConfig,
    Simulator,
    StaticPolicy,
    WorkloadMix,
)

WORKLOAD_CYCLE = ("qalike", "codelike", "mathlike", "summlike")


def _pool_profile() -> Profile:
    return Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                  value_bits=8, granularity="per_channel",
                                  codec="zstd3"),
                   cr=3.0, s_enc=5e8, s_dec=5e8)


# ---------------------------------------------------------------------------
def run_runtime(smoke: bool = False) -> None:
    from repro.serving.engine import RuntimeConfig, ServingRuntime

    cfg = RuntimeConfig(seq=32 if smoke else 96, decode_tokens=4 if smoke else 8,
                        prefill_tok_s=2000.0, decode_tok_s=500.0)
    rt = ServingRuntime(
        static_profile=_pool_profile(), config=cfg,
        trace=BandwidthTrace.constant(1 * GBPS),
        scheduler=SchedulerConfig(max_slots=6, max_prefills_per_step=2,
                                  max_queue=64))
    # 12 requests over 4 workloads; repeated prompt seeds => pool hits.
    t0 = time.perf_counter()
    for i, w in enumerate(WORKLOAD_CYCLE * 3):
        rt.submit(w, slo_class=("interactive", "standard", "batch")[i % 3],
                  prompt_seed=i % 4)
        rt.step()
    rt.run()
    us = (time.perf_counter() - t0) * 1e6
    s = rt.summary()
    assert s["max_in_flight"] >= 4, s
    assert s["mean_ttft_hit"] < s["mean_ttft_cold"], s
    emit("runtime_continuous_batching", us,
         f"completed={s['completed']} max_in_flight={s['max_in_flight']} "
         f"pool_hit_rate={s['pool_hit_rate']:.2f} "
         f"ttft_hit={s['mean_ttft_hit']*1e3:.1f}ms "
         f"ttft_cold={s['mean_ttft_cold']*1e3:.1f}ms "
         f"speedup={s['mean_ttft_cold']/s['mean_ttft_hit']:.1f}x")


# ---------------------------------------------------------------------------
def run_slots_sweep(smoke: bool = False,
                    slot_counts: Sequence[int] = (1, 2, 4, 8)) -> Dict[int, Dict[str, float]]:
    """Per-iteration decode wall-clock vs active slot count, arena vs the
    PR-1 per-slot loop, with a token-exact parity check."""
    import jax.numpy as jnp
    from repro.core.quality import (_jitted_steps, _prompts_for,
                                    copy_cache_slot, get_reference_model)
    from repro.models.transformer import init_cache

    seq = 24 if smoke else 64
    steps = 6 if smoke else 16
    cfg, params = get_reference_model()
    max_len = seq + steps + 2
    pre1, dec1, _ = _jitted_steps(cfg.name, seq, 1, max_len)

    # One batch-1 prefill per slot, shared by both decode paths.
    caches1, firsts = [], []
    for i in range(max(slot_counts)):
        tokens, _ = _prompts_for(WORKLOAD_CYCLE[i % 4], 1, seq, seed=i)
        logits, c = pre1(params, {"tokens": tokens})
        caches1.append(c)
        firsts.append(int(np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))[0]))

    results: Dict[int, Dict[str, float]] = {}
    for n in slot_counts:
        # ---- batched arena: ONE masked jitted call per iteration ----
        _, _, arena_dec = _jitted_steps(cfg.name, seq, n, max_len)
        arena = init_cache(cfg, n, max_len)
        for i in range(n):
            arena = copy_cache_slot(cfg, arena, caches1[i], i)
        pos = np.full(n, seq, np.int32)
        last = np.asarray(firsts[:n], np.int32)
        mask = jnp.ones(n, bool)
        arena_toks: List[List[int]] = [[int(f)] for f in last]
        arena_times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            nxt, arena = arena_dec(params, arena, jnp.asarray(last[:, None]),
                                   jnp.asarray(pos), mask)
            nxt = np.asarray(nxt)[:, 0]   # the iteration's single host pull
            arena_times.append(time.perf_counter() - t0)
            for i in range(n):
                arena_toks[i].append(int(nxt[i]))
                last[i] = nxt[i]
                pos[i] += 1

        # ---- PR-1 loop: batch-1 call + host argmax per slot ----
        loop_caches = list(caches1[:n])
        loop_toks: List[List[int]] = [[int(f)] for f in firsts[:n]]
        loop_times = []
        for t in range(steps):
            t0 = time.perf_counter()
            for i in range(n):
                logits, loop_caches[i] = dec1(
                    params, loop_caches[i],
                    jnp.asarray([[loop_toks[i][-1]]], jnp.int32),
                    jnp.asarray(seq + t, jnp.int32))
                loop_toks[i].append(int(np.asarray(
                    jnp.argmax(logits[:, -1, :], axis=-1))[0]))
            loop_times.append(time.perf_counter() - t0)

        # token-exact parity vs the pre-refactor decode path
        assert arena_toks == loop_toks, f"token mismatch at n={n}"

        # medians: first iterations absorb jit compilation
        arena_ms = float(np.median(arena_times) * 1e3)
        loop_ms = float(np.median(loop_times) * 1e3)
        results[n] = {"arena_ms": arena_ms, "loop_ms": loop_ms}
        emit(f"slots_sweep_n{n}", arena_ms * 1e3,
             f"arena_ms_per_step={arena_ms:.3f} "
             f"per_slot_loop_ms_per_step={loop_ms:.3f} "
             f"token_parity=exact")

    lo, hi = min(slot_counts), max(slot_counts)
    arena_ratio = results[hi]["arena_ms"] / results[lo]["arena_ms"]
    loop_ratio = results[hi]["loop_ms"] / results[lo]["loop_ms"]
    emit("slots_sweep_scaling", 0.0,
         f"arena_{hi}v{lo}_ratio={arena_ratio:.2f} "
         f"loop_{hi}v{lo}_ratio={loop_ratio:.2f}")
    if not smoke:
        # Acceptance: batched decode amortizes across slots (≤2× at 8
        # slots), where the per-slot loop degraded ~linearly.
        assert arena_ratio <= 2.0, results
    return results


# ---------------------------------------------------------------------------
def run_sweep(smoke: bool = False) -> None:
    # 4-bit + zstd pool profile: a fetch moves ~1/6 of the KV bytes.
    prof = Profile(StrategyConfig(quantizer="uniform", key_bits=4,
                                  value_bits=4, granularity="per_channel",
                                  codec="zstd3"),
                   cr=6.0, s_enc=1e9, s_dec=1e9)
    trace = BandwidthTrace.constant(1 * GBPS)
    mixes = {
        "uniform": None,
        "tiered": {"interactive": 0.3, "standard": 0.4, "batch": 0.3},
    }
    n_requests = 30 if smoke else 120
    rates = (2.0,) if smoke else (0.5, 2.0, 8.0)
    # 4 prefill nodes x 2000 tok/s over ~4k-token prompts => capacity
    # ~2 req/s: the rates bracket under-load, saturation, and overload.
    for rate in rates:
        for mix_name, mix in mixes.items():
            for cap_name, cap in (("small", int(5e8)), ("large", 1 << 36)):
                reqs = WorkloadMix(rate=rate, seed=11, q_min=0.0,
                                   ctx_scale=0.25, prefix_hit_rate=0.7,
                                   slo_class_mix=mix).generate(n_requests)
                store = PrefixKVStore(capacity_bytes=cap, block=1)
                t0 = time.perf_counter()
                res = Simulator(
                    SimConfig(scenario="pool", prefill_tok_s=2000.0),
                    StaticPolicy(prof, "pool"), trace, reqs, store=store,
                    scheduler=SchedulerConfig(max_queue=40),
                ).run()
                us = (time.perf_counter() - t0) * 1e6
                done = res.completed()
                # Three-way: full hits (fetch only), partial hits (fetch +
                # top-up prefill for the uncovered suffix), cold recomputes.
                fetched = lambda r: r.breakdown.get("comm", 0) > 0
                refill = lambda r: r.breakdown.get("prefill", 0) > 0
                hits = [r for r in done if fetched(r) and not refill(r)]
                partial = [r for r in done if fetched(r) and refill(r)]
                colds = [r for r in done if refill(r) and not fetched(r)]
                mean = lambda rs: (float(np.mean([r.ttft for r in rs]))
                                   if rs else 0.0)
                emit(f"sweep_rate{rate:g}_{mix_name}_{cap_name}", us,
                     f"hit_rate={store.stats.hit_rate:.2f} "
                     f"evictions={store.stats.evictions} "
                     f"rejected={len(res.rejected())} "
                     f"ttft_hit={mean(hits):.3f}s "
                     f"ttft_partial={mean(partial):.3f}s(n={len(partial)}) "
                     f"ttft_cold={mean(colds):.3f}s "
                     f"p95_ttft={np.percentile(res.ttft(), 95):.3f}s")


def run(smoke: bool = False) -> None:
    run_sweep(smoke)
    run_runtime(smoke)
    run_slots_sweep(smoke)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized settings; crash = fail, timing advisory")
    ap.add_argument("--json", default="",
                    help="archive emitted rows to this JSON path")
    args = ap.parse_args(argv)
    run(smoke=args.smoke)
    if args.json:
        write_json(args.json)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
