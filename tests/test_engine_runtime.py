"""Continuous-batching ServingRuntime e2e on the real tiny model."""
import numpy as np
import pytest

from repro.core.profiles import Profile
from repro.core.strategy import StrategyConfig
from repro.serving import BandwidthTrace, GBPS, PrefixKVStore, SchedulerConfig


def _profile():
    # 8-bit per-channel: real compression on the pool path, near-lossless.
    return Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                  value_bits=8, granularity="per_channel"),
                   cr=2.0, s_enc=5e8, s_dec=5e8)


def _runtime(reference_model, **kw):
    from repro.serving.engine import RuntimeConfig, ServingRuntime
    cfg = RuntimeConfig(seq=64, decode_tokens=6,
                        prefill_tok_s=2000.0, decode_tok_s=500.0)
    defaults = dict(
        static_profile=_profile(), config=cfg,
        trace=BandwidthTrace.constant(1 * GBPS),
        scheduler=SchedulerConfig(max_slots=6, max_prefills_per_step=2,
                                  max_queue=32))
    defaults.update(kw)
    rt = ServingRuntime(**defaults)
    # pin the session-cached reference model (avoids retraining paths)
    rt.model_cfg, rt.params = reference_model
    return rt


@pytest.mark.slow
def test_pool_hit_beats_cold_prefill_ttft(reference_model):
    """The paper's TTFT path: a prefix-pool hit (fetch real compressed
    bytes + decompress + inject) must beat recomputing prefill."""
    rt = _runtime(reference_model)
    cold_rid = rt.submit("qalike", prompt_seed=42)
    rt.run()
    assert len(rt.store) == 1  # prefix written back to the pool
    hit_rid = rt.submit("qalike", prompt_seed=42)  # identical prompt
    rt.run()

    by_rid = {r.rid: r for r in rt.completed}
    cold, hit = by_rid[cold_rid], by_rid[hit_rid]
    assert not cold.pool_hit and hit.pool_hit
    assert hit.ttft < cold.ttft
    assert hit.breakdown["comm"] > 0 and hit.breakdown.get("prefill", 0) == 0
    assert cold.breakdown["prefill"] > 0
    assert cold.t_pool_write > 0 and hit.t_pool_write == 0
    # real bytes moved: the hit fetched exactly what the cold request stored
    assert hit.wire_bytes == cold.wire_bytes > 0
    assert hit.wire_bytes < cold.kv_bytes  # compressed on the wire
    # both generated a full completion
    assert len(hit.tokens) == len(cold.tokens) == rt.cfg.decode_tokens + 1
    assert rt.store.stats.hits == 1


@pytest.mark.slow
def test_runtime_sustains_concurrent_in_flight_requests(reference_model):
    rt = _runtime(reference_model)
    rids = [rt.submit(w, prompt_seed=i) for i, w in enumerate(
        ("qalike", "codelike", "mathlike", "summlike", "qalike", "codelike"))]
    assert all(r is not None for r in rids)
    done = rt.run()
    assert len(done) == 6
    assert rt.max_in_flight() >= 4  # continuous batching, not one-by-one
    for r in done:
        assert r.jct >= r.ttft > 0
        total = sum(r.breakdown.values())
        assert total == pytest.approx(r.jct, abs=1e-6), (r.breakdown, r.jct)


@pytest.mark.slow
def test_runtime_admission_and_slo_priority(reference_model):
    rt = _runtime(reference_model,
                  scheduler=SchedulerConfig(max_slots=2,
                                            max_prefills_per_step=1,
                                            max_queue=4, aging_s=0.0))
    assert rt.submit("qalike", slo_class="batch", prompt_seed=0) is not None
    assert rt.submit("qalike", slo_class="batch", prompt_seed=1) is not None
    assert rt.submit("qalike", slo_class="batch", prompt_seed=2) is not None
    assert rt.submit("qalike", slo_class="interactive",
                     prompt_seed=3) is not None
    # queue bound (4) reached -> load shed
    assert rt.submit("qalike", slo_class="batch", prompt_seed=4) is None
    rt.run()
    assert len(rt.completed) == 4
    # the interactive request jumped the batch queue: first token first
    inter = [r for r in rt.completed if r.slo_class == "interactive"][0]
    batch_ttfts = [r.ttft for r in rt.completed if r.slo_class == "batch"]
    assert inter.ttft <= min(batch_ttfts)


@pytest.mark.slow
def test_store_eviction_under_tiny_capacity(reference_model):
    store = PrefixKVStore(capacity_bytes=40_000, block=16)
    rt = _runtime(reference_model, store=store)
    for i in range(4):
        rt.submit("codelike", prompt_seed=100 + i)
        rt.run()
    assert store.used_bytes <= store.capacity_bytes
    assert store.stats.evictions > 0 or store.stats.rejected_puts > 0


# ---------------------------------------------------------------------------
# Batched slot-arena decode (PR 2)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_token_exact_parity_with_pr1_fixture(reference_model):
    """The batched arena decode must emit exactly the tokens the PR-1
    per-slot loop emitted (fixture pinned before the refactor) across a
    pool hit/miss mix with staggered admissions."""
    import json
    from _runtime_scenario import (FIXTURE, build_runtime, params_digest,
                                   run_scenario)
    fix = json.loads(FIXTURE.read_text())
    rt = build_runtime(reference_model)
    if params_digest(rt.params) != fix["params_digest"]:
        pytest.skip("reference model differs from the fixture's "
                    "(e.g. CI trains a smaller REPRO_REF_STEPS model)")
    out = run_scenario(rt)
    assert set(out) == set(fix["outputs"])
    for rid, rec in fix["outputs"].items():
        assert out[rid]["pool_hit"] == rec["pool_hit"], rid
        assert out[rid]["tokens"] == rec["tokens"], rid


@pytest.mark.slow
def test_arena_decode_token_exact_vs_per_slot_loop(reference_model):
    """Decode-path equivalence, independent of the trained model: the
    masked batched arena step must reproduce the PR-1 per-slot batch-1
    decode loop token-for-token, including a lossy pool-style injection
    and staggered slot activation (mask churn)."""
    import jax.numpy as jnp
    from repro.core.pipeline import CompressionPipeline
    from repro.core.quality import (_jitted_steps, _prompts_for,
                                    copy_cache_slot, extract_kv, inject_kv)
    from repro.core.strategy import StrategyConfig
    from repro.models.transformer import init_cache

    cfg, params = reference_model
    seq, n_slots, steps = 48, 4, 6
    max_len = seq + steps + 2
    pre1, dec1, _ = _jitted_steps(cfg.name, seq, 1, max_len)
    _, _, arena_dec = _jitted_steps(cfg.name, seq, n_slots, max_len)

    slot_caches, firsts = [], []
    for i, w in enumerate(("qalike", "codelike", "mathlike", "summlike")):
        tokens, _ = _prompts_for(w, 1, seq, seed=i)
        logits, caches = pre1(params, {"tokens": tokens})
        first = int(np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))[0])
        if i == 3:  # pool-hit-like slot: lossy compress->decompress->inject
            pipe = CompressionPipeline(StrategyConfig(
                quantizer="uniform", key_bits=8, value_bits=8,
                granularity="per_channel"))
            kv = extract_kv(cfg, caches, 0, upto=seq)
            restored = pipe.decompress(pipe.compress(kv))
            caches = inject_kv(cfg, init_cache(cfg, 1, max_len), 0, restored)
        slot_caches.append(caches)
        firsts.append(first)

    # ---- reference: PR-1 style per-slot batch-1 decode loops ----
    ref_tokens = []
    for caches, first in zip(slot_caches, firsts):
        toks, c = [first], caches
        for t in range(steps):
            logits, c = dec1(params, c, jnp.asarray([[toks[-1]]], jnp.int32),
                             jnp.asarray(seq + t, jnp.int32))
            toks.append(int(np.asarray(
                jnp.argmax(logits[:, -1, :], axis=-1))[0]))
        ref_tokens.append(toks)

    # ---- batched arena, slot i activating at iteration i ----
    arena = init_cache(cfg, n_slots, max_len)
    for i, caches in enumerate(slot_caches):
        arena = copy_cache_slot(cfg, arena, caches, i)
    pos = np.full(n_slots, seq, np.int32)
    last = np.asarray(firsts, np.int32)
    got = [[f] for f in firsts]
    it = 0
    while any(len(g) < steps + 1 for g in got):
        mask = np.array([i <= it and len(got[i]) < steps + 1
                         for i in range(n_slots)])
        nxt, arena = arena_dec(params, arena, jnp.asarray(last[:, None]),
                               jnp.asarray(pos), jnp.asarray(mask))
        nxt = np.asarray(nxt)[:, 0]
        for i in range(n_slots):
            if mask[i]:
                got[i].append(int(nxt[i]))
                last[i] = nxt[i]
                pos[i] += 1
        it += 1
    assert got == ref_tokens


class _SpyController:
    """Static-profile controller that records every observe() call."""

    def __init__(self, profile):
        self._profile = profile
        self.observed = []

    def select(self, ctx):
        from repro.controller import Decision
        return Decision(self._profile, 0, 0, 0.0)

    def observe(self, ctx, decision, latency):
        self.observed.append(float(latency))


@pytest.mark.slow
def test_runtime_observes_critical_path_latency(reference_model):
    """Regression (PR 2): the miss path used to feed the bandit
    t_compress + t_comm of the *off-critical-path pool write*; it must
    observe the request's realized critical path.  Since PR 3 the SLO
    metric is explicit: with slo_metric="jct" the observation is the
    breakdown sum (== jct), never the off-path pool write."""
    spy = _SpyController(_profile())
    rt = _runtime(reference_model, controller=spy, static_profile=None)
    rt.submit("qalike", prompt_seed=7, slo_metric="jct")
    rt.run()
    (r,) = rt.completed
    assert not r.pool_hit
    assert len(spy.observed) == 1
    assert spy.observed[0] == pytest.approx(sum(r.breakdown.values()),
                                            abs=1e-9)
    assert spy.observed[0] == pytest.approx(r.jct, abs=1e-9)
    assert r.t_pool_write > 0  # off-path cost exists but is not charged
    # pool hit: no controller decision is made -> nothing observed
    rt.submit("qalike", prompt_seed=7)
    rt.run()
    assert rt.completed[-1].pool_hit
    assert len(spy.observed) == 1


@pytest.mark.slow
def test_runtime_slo_metric_matches_observation(reference_model):
    """Bugfix (PR 3): _finish used to flag slo_violated on TTFT while the
    bandit guardrail compared the observed latency (JCT) to the same
    t_slo.  Both now use the request's resolved slo_metric: pool-scenario
    default is ttft (observation == ttft), and a request pinning jct is
    both flagged and observed on jct."""
    spy = _SpyController(_profile())
    rt = _runtime(reference_model, controller=spy, static_profile=None)
    rt.submit("qalike", prompt_seed=11)   # pool default -> ttft
    rt.run()
    (r,) = rt.completed
    assert len(spy.observed) == 1
    assert spy.observed[0] == pytest.approx(r.ttft, abs=1e-9)
    assert spy.observed[0] < r.jct  # ttft is a strict prefix of jct here

    # a tight TTFT SLO violated by the cold prefill: flag and observation
    # agree (pre-fix, cooldown bookkeeping used jct while the runtime
    # reported ttft violations)
    spy2 = _SpyController(_profile())
    rt2 = _runtime(reference_model, controller=spy2, static_profile=None)
    rt2.submit("qalike", prompt_seed=12, t_slo=1e-6)
    rt2.run()
    (r2,) = rt2.completed
    assert r2.slo_violated and spy2.observed[0] == pytest.approx(r2.ttft,
                                                                 abs=1e-9)
    assert spy2.observed[0] > 1e-6


@pytest.mark.slow
def test_disaggregated_engine_observes_on_path_comm(reference_model):
    """One-shot PD path: compress/comm/decompress ARE on the critical
    path, so the observed latency equals that breakdown sum."""
    from repro.serving.engine import DisaggregatedEngine
    spy = _SpyController(_profile())
    eng = DisaggregatedEngine(controller=spy, seq=48, decode_tokens=4,
                              batch=2)
    b = eng.serve("qalike", BandwidthTrace.constant(1 * GBPS))
    assert len(spy.observed) == 1
    assert spy.observed[0] == pytest.approx(
        b.t_prefill + b.t_compress + b.t_comm + b.t_decompress, abs=1e-9)


@pytest.mark.slow
def test_run_budget_is_relative_to_the_call(reference_model):
    """Regression (PR 2): run(max_steps) compared against the cumulative
    step counter, so a second run() on a long-lived runtime returned
    immediately with work still queued."""
    rt = _runtime(reference_model)
    rt.submit("qalike", prompt_seed=0)
    rt.run(max_steps=3)
    assert rt.steps == 3 and not rt.scheduler.idle
    rt.run(max_steps=3)   # pre-fix: no-op (steps 3 >= budget 3)
    assert rt.steps == 6
    rt.run()
    assert rt.scheduler.idle and len(rt.completed) == 1


@pytest.mark.slow
def test_arena_slot_recycling(reference_model):
    """More requests than slots: slot ids stay in range, get recycled,
    and all return to the scheduler's free pool when idle."""
    rt = _runtime(reference_model)   # max_slots = 6
    for i, w in enumerate(("qalike", "codelike", "mathlike", "summlike",
                           "qalike", "codelike", "mathlike", "summlike")):
        rt.submit(w, prompt_seed=i)
    done = rt.run()
    assert len(done) == 8
    assert all(0 <= r.slot < rt.n_slots for r in done)
    assert len({r.slot for r in done}) <= rt.n_slots < len(done)
    assert sorted(rt.scheduler._free_slots) == list(range(rt.n_slots))
