"""Speculative + lookahead decoding (DESIGN.md §15).

The load-bearing guarantee is exactness: with greedy verification, a
speculative run emits the tokens the width-1 step of the SAME model
would — drafts only change how many serial decode steps it takes.  The
width-w step reduces in another order than the width-1 step, so the
pinned scenario of ``_runtime_scenario`` is run speculatively across
k ∈ {2, 4} × {dense, paged} × {pool, pd} and held to the width-1 step at
a stated logit tolerance (``_assert_width_one_exact``); the
fixture-parity variant pins against the PR-1 tokens when the trained
reference model matches the fixture's digest.

The n-gram proposer carries the exactness tests: its drafts reuse the
target's own committed history, so every accepted position's KV row was
computed from the token the target itself emitted.  The two-model path
is exercised for dataflow (deep accepts, multi-token commits) with a
high-agreement assertion instead — committed rows from a width-(k+1)
verify can differ from sequentially-written rows by ~1 bf16 ulp (the
online-softmax merge associates differently), which can flip greedy
argmax near-ties far downstream; see DESIGN.md §15.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _runtime_scenario import SCENARIO, build_runtime, run_scenario
from repro.core.strategy import StrategyConfig
from repro.serving.speculative import NGramDraft, accept_length


# ---------------------------------------------------------------------------
# Pure units: accept rule + n-gram proposer
# ---------------------------------------------------------------------------
def test_accept_length_is_longest_matching_prefix():
    assert accept_length([], [7]) == 0
    assert accept_length([3, 5], [3, 5, 9]) == 2
    assert accept_length([3, 5], [3, 6, 9]) == 1
    assert accept_length([4, 5], [3, 5, 9]) == 0


def test_ngram_draft_proposes_most_recent_continuation():
    d = NGramDraft(max_ngram=2)
    d.start(0, 42, [1, 2, 9], first=1)
    # history 1 2 9 1 — suffix 1-gram "1" last continued with 2
    out = d.propose_all([(0, 42, 1, 4)], {0: 3})
    assert out[0] == [2, 9, 1]
    # 2-gram beats 1-gram: after committing 2, suffix "1 2" matches pos 0-1
    d.commit(0, 42, [2])
    out = d.propose_all([(0, 42, 2, 5)], {0: 2})
    assert out[0] == [9, 1]
    # unseen suffix -> no drafts; the slot decodes plainly that iteration
    d.commit(0, 42, [77])
    assert d.propose_all([(0, 42, 77, 6)], {0: 4})[0] == []


def test_ngram_draft_state_is_per_request():
    d = NGramDraft()
    d.start(0, 1, [5, 6], first=5)
    d.start(1, 2, [8, 8], first=8)
    out = d.propose_all([(0, 1, 5, 3), (1, 2, 8, 3)], {0: 2, 1: 2})
    assert out[0] == [6, 5]
    assert out[1] == [8]    # continuation truncated at end of history
    d.stop(0, 1)
    assert d.propose_all([(0, 1, 5, 3)], {0: 2})[0] == []


# ---------------------------------------------------------------------------
# Exactness to the width-1 step, on the real tiny model
# ---------------------------------------------------------------------------
# The verify step (width w = 1 + drafts) and the width-1 step reduce in
# different orders (DESIGN.md §15), and the byte LM's logits are bf16, so
# on the same inputs their logits differ by a few bf16 ulps: the widest
# difference measured (_widest_width_difference, printed) is 1.5, three
# ulps of a top logit near 83, on the CPU.  A token the wide step chose
# is within twice that difference of the width-1 step's best logit at
# its position, so LOGIT_TOL is twice 1.5.
LOGIT_TOL = 3.0
# The scenario's profile: the KV a pool hit decodes from is its restore.
STRATEGY = StrategyConfig(quantizer="uniform", key_bits=8, value_bits=8,
                          granularity="per_channel")
_BASELINES = {}
_WIDEST = []


def _steps(reference_model, rt):
    from repro.core.quality import _jitted_steps
    return _jitted_steps(reference_model[0].name, rt.cfg.seq, 1,
                         rt.cfg.arena_max_len)


def _prefilled(reference_model, rt, rid: int, pool_hit: bool):
    """The prefill's last logits of request ``rid`` of the scenario, and
    the batch-1 cache it decoded from: the prefill's, with the profile's
    restore for a pool hit."""
    from repro.core.pipeline import CompressionPipeline
    from repro.core.quality import _prompts_for, extract_kv, inject_kv

    cfg, params = reference_model
    pre, _, _ = _steps(reference_model, rt)
    workload, _, seed, _, _ = SCENARIO[rid]
    prompt, _ = _prompts_for(workload, 1, rt.cfg.seq, seed)
    logits, cache = pre(params, {"tokens": prompt})
    if pool_hit:
        pipe = CompressionPipeline(STRATEGY)
        kv = extract_kv(cfg, cache, 0, upto=rt.cfg.seq)
        cache = inject_kv(cfg, cache, 0, pipe.decompress(pipe.compress(kv)))
    return np.asarray(logits[0, -1], np.float32), cache


def _width_one_logits(reference_model, rt, cache, tokens, start=0):
    """The width-1 step's logits after each of ``tokens``, teacher-forced
    from position ``seq + start``: ((len(tokens), V), the cache after)."""
    _, dec, _ = _steps(reference_model, rt)
    rows = []
    for j, t in enumerate(tokens):
        logits, cache = dec(reference_model[1], cache,
                            jnp.asarray([[t]], jnp.int32),
                            jnp.asarray(rt.cfg.seq + start + j, jnp.int32))
        rows.append(np.asarray(logits[0, -1], np.float32))
    return np.stack(rows), cache


def _widest_width_difference(reference_model, rt) -> float:
    """The widest |logit| difference between a width-3 or width-5 step and
    the width-1 steps over the same cache and tokens: greedy
    continuations of the scenario's five distinct prompts, from the end
    of the prompt and one token on.  Printed once."""
    if not _WIDEST:
        _, dec, _ = _steps(reference_model, rt)
        widest = 0.0
        for rid in (0, 1, 2, 4, 7):
            first, cache = _prefilled(reference_model, rt, rid, False)
            toks, c = [int(first.argmax())], cache
            for j in range(6):
                row, c = _width_one_logits(reference_model, rt, c,
                                           toks[-1:], start=j)
                toks.append(int(row[-1].argmax()))
            narrow, after_one = _width_one_logits(reference_model, rt,
                                                  cache, toks[:1])
            narrow = np.concatenate([narrow, _width_one_logits(
                reference_model, rt, after_one, toks[1:6], start=1)[0]])
            for start, c in ((0, cache), (1, after_one)):
                for width in (3, 5):
                    logits, _ = dec(
                        reference_model[1], c,
                        jnp.asarray([toks[start:start + width]], jnp.int32),
                        jnp.asarray(rt.cfg.seq + start, jnp.int32))
                    widest = max(widest, float(np.abs(
                        np.asarray(logits[0], np.float32)
                        - narrow[start:start + width]).max()))
        print(f"widest logit difference, width 3/5 vs 1: {widest}")
        _WIDEST.append(widest)
    return _WIDEST[0]


def _assert_width_one_exact(reference_model, rt, out, base):
    """The bench's check in miniature: each request's tokens, the
    committed ones teacher-forced through the width-1 step over the KV
    the request decoded from, are each within LOGIT_TOL of that
    position's best logit, and equal to the argmax up to the first
    position whose top two logits are closer than LOGIT_TOL.  The run
    serves the plain run's requests, hits and lengths."""
    assert _widest_width_difference(reference_model, rt) <= LOGIT_TOL
    shape = {rid: (r["workload"], r["pool_hit"], len(r["tokens"]))
             for rid, r in base.items()}
    assert {rid: (r["workload"], r["pool_hit"], len(r["tokens"]))
            for rid, r in out.items()} == shape
    for rid, rec in out.items():
        toks = rec["tokens"]
        first, cache = _prefilled(reference_model, rt, int(rid),
                                  rec["pool_hit"])
        logits, _ = _width_one_logits(reference_model, rt, cache, toks[:-1])
        logits = np.concatenate([first[None], logits])   # row j: toks[j]
        gap = logits.max(-1) - logits[np.arange(len(toks)), toks]
        assert gap.max() <= LOGIT_TOL, (rid, gap)
        top2 = np.sort(logits, -1)[:, -2:]
        near = np.flatnonzero(top2[:, 1] - top2[:, 0] < LOGIT_TOL)
        upto = near[0] if near.size else len(toks)
        assert toks[:upto] == logits[:upto].argmax(-1).tolist(), rid


def _baseline(reference_model, mode, paged):
    key = (mode, paged)
    if key not in _BASELINES:
        rt = build_runtime(reference_model, mode=mode, paged=paged)
        _BASELINES[key] = run_scenario(rt)
    return _BASELINES[key]


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["pool", "pd"])
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("k", [2, 4])
def test_ngram_speculation_token_exact_vs_plain_decode(
        reference_model, mode, paged, k):
    base = _baseline(reference_model, mode, paged)
    rt = build_runtime(reference_model, mode=mode, paged=paged, spec_k=k)
    out = run_scenario(rt)
    _assert_width_one_exact(reference_model, rt, out, base)
    # speculation actually engaged and compressed serial steps
    done = rt.completed
    steps = sum(r.verify_steps for r in done)
    committed = sum(r.spec_committed for r in done)
    assert steps > 0 and committed > steps
    # the summary carries the acceptance block (satellite 1)
    s = rt.summary()
    assert s["spec_tokens_per_step"] == pytest.approx(committed / steps)
    assert 0.0 <= s["spec_accept_rate"] <= 1.0


@pytest.mark.slow
@pytest.mark.parametrize("k", [2, 4])
def test_speculative_parity_with_pr1_fixture(reference_model, k):
    """Same pin as test_token_exact_parity_with_pr1_fixture, speculation
    on: the PR-1 per-slot loop's tokens, bit for bit."""
    import json
    from _runtime_scenario import FIXTURE, params_digest
    fix = json.loads(FIXTURE.read_text())
    rt = build_runtime(reference_model, spec_k=k)
    if params_digest(rt.params) != fix["params_digest"]:
        pytest.skip("reference model differs from the fixture's "
                    "(e.g. CI trains a smaller REPRO_REF_STEPS model)")
    out = run_scenario(rt)
    for rid, rec in fix["outputs"].items():
        assert out[rid]["tokens"] == rec["tokens"], rid


@pytest.mark.slow
def test_spec_k_zero_is_the_plain_path(reference_model):
    """k = 0 must not merely produce the same tokens — it must BE the
    non-speculative path: legacy arena geometry, zero verify steps, no
    speculation keys in the summary."""
    from repro.serving.engine import RuntimeConfig
    assert RuntimeConfig(seq=64, decode_tokens=6).arena_max_len == \
        RuntimeConfig(seq=64, decode_tokens=6, spec_k=0).arena_max_len
    rt = build_runtime(reference_model, spec_k=0)
    out = run_scenario(rt)
    assert out == _baseline(reference_model, "pool", False)
    assert all(r.verify_steps == 0 and r.spec_committed == 0
               for r in rt.completed)
    assert "spec_tokens_per_step" not in rt.summary()


@pytest.mark.slow
def test_model_draft_path_dataflow(reference_model):
    """Two-model path with the target as its own draft: acceptance is
    near-1, so verify steps commit multi-token runs and the serial step
    count collapses.  Exactness is asserted only to high agreement — the
    bf16 merge-ulp caveat above — plus first-token equality per request
    (prefill is untouched by speculation)."""
    base = _baseline(reference_model, "pool", False)
    rt = build_runtime(reference_model, spec_k=4, spec_kind="model")
    out = run_scenario(rt)
    assert set(out) == set(base)
    agree = total = 0
    for rid, rec in base.items():
        a, b = rec["tokens"], out[rid]["tokens"]
        assert len(a) == len(b)
        assert a[0] == b[0], rid
        agree += sum(int(x == y) for x, y in zip(a, b))
        total += len(a)
    assert agree / total >= 0.9, (agree, total)
    done = rt.completed
    steps = sum(r.verify_steps for r in done)
    committed = sum(r.spec_committed for r in done)
    assert committed / steps > 2.0   # deep accepts, not 1-token crawl

# ---------------------------------------------------------------------------
# Controller: adaptive speculation length
# ---------------------------------------------------------------------------
def _spec_controller(cands=(0, 2, 4), **kw):
    from repro.controller import ServiceAwareController
    return ServiceAwareController({}, spec_candidates=cands, **kw)


def _ctx(workload="qalike", route="", decode_time=1.0):
    from repro.controller import ServiceContext
    return ServiceContext(workload=workload, bandwidth=1e9, t_slo=0.0,
                          q_min=0.0, decode_time=decode_time, route=route)


def test_tokens_per_step_model_is_the_geometric_series():
    from repro.controller import expected_tokens_per_step as tps
    assert tps(0, 0.7) == 1.0
    assert tps(3, 0.0) == 1.0
    assert tps(2, 1.0) == 3.0
    assert tps(2, 0.5) == pytest.approx(1 + 0.5 + 0.25)


def test_controller_falls_back_to_plain_decode_at_zero_accept():
    c = _spec_controller(spec_accept_prior=0.0)
    assert c.select(_ctx()).spec_k == 0


def test_controller_picks_max_k_at_high_accept():
    c = _spec_controller(spec_accept_prior=1.0)
    assert c.select(_ctx()).spec_k == 4
    # unknown decode time still ranks candidates (scale-free objective)
    assert c.select(_ctx(decode_time=0.0)).spec_k == 4


def test_controller_verify_overhead_caps_k():
    from repro.controller import speculative_decode_latency as sdl
    # at accept .5, one extra draft buys <.25 tokens past k=2 but costs
    # 10% verify overhead per draft position -> k should not run away
    lats = {k: sdl(1.0, k, 0.5, verify_overhead=0.1) for k in (0, 2, 4, 8)}
    assert min(lats, key=lats.get) in (2, 4)
    assert lats[8] > lats[4]


def test_accept_rate_is_learned_per_workload_route():
    c = _spec_controller(spec_accept_prior=0.5, spec_accept_alpha=0.2)
    assert c.accept_rate("codelike", "p0->d0") == 0.5
    c.observe_accept("codelike", "p0->d0", 1.0)
    assert c.accept_rate("codelike", "p0->d0") == 1.0   # first obs replaces
    c.observe_accept("codelike", "p0->d0", 0.0)
    assert c.accept_rate("codelike", "p0->d0") == pytest.approx(0.8)
    # other (workload, route) keys untouched
    assert c.accept_rate("codelike", "p0->d1") == 0.5
    assert c.accept_rate("qalike", "p0->d0") == 0.5
    # learned rate drives k-selection on that route only
    lo = _spec_controller(spec_accept_prior=0.5)
    for _ in range(30):
        lo.observe_accept("qalike", "", 0.0)
    assert lo.select(_ctx()).spec_k == 0
    assert lo.select(_ctx(route="p0->d9")).spec_k > 0


@pytest.mark.slow
def test_adaptive_spec_k_flows_controller_to_slots(reference_model):
    """cfg.spec_adaptive: each slot's draft budget is the controller
    decision's spec_k (capped at cfg.spec_k), and _finish feeds realized
    accept rates back through observe_accept."""
    from repro.controller import Decision
    from repro.core.profiles import Profile
    from repro.core.strategy import StrategyConfig

    class SpySpecController:
        def __init__(self, profile, spec_k):
            self._profile = profile
            self._spec_k = spec_k
            self.accepts = []

        def select(self, ctx):
            return Decision(self._profile, 0, 0, 0.0, spec_k=self._spec_k)

        def observe(self, ctx, decision, latency):
            pass

        def observe_accept(self, workload, route, rate):
            self.accepts.append((workload, route, rate))

    profile = Profile(
        StrategyConfig(quantizer="uniform", key_bits=8, value_bits=8,
                       granularity="per_channel"),
        cr=2.0, s_enc=5e8, s_dec=5e8)
    spy = SpySpecController(profile, spec_k=7)   # above the cap
    rt = build_runtime(reference_model, spec_k=3, spec_adaptive=True)
    rt.static_profile = None
    rt.controller = spy
    for pw in rt.prefill_workers:
        pw.controller = spy
    out = run_scenario(rt)
    _assert_width_one_exact(reference_model, rt, out,
                            _baseline(reference_model, "pool", False))
    # the controller's pick was capped at cfg.spec_k
    assert all(r.spec_k == 3 for r in rt.completed if not r.pool_hit)
    # realized accept rates fed back for every request that offered drafts
    offered = [r for r in rt.completed if r.drafts_offered > 0]
    assert offered and len(spy.accepts) == len(offered)
    assert all(0.0 <= rate <= 1.0 for _, _, rate in spy.accepts)


# ---------------------------------------------------------------------------
# Metrics: the acceptance block
# ---------------------------------------------------------------------------
class _Rec:
    def __init__(self, **kw):
        self.ttft = kw.pop("ttft", 0.1)
        self.jct = kw.pop("jct", 0.2)
        self.slo_class = kw.pop("slo_class", "standard")
        self.t_slo = 0.0
        self.slo_violated = False
        self.__dict__.update(kw)


def test_speculation_stats_aggregates_per_class():
    from repro.serving.metrics import latency_summary, speculation_stats
    reqs = [
        _Rec(slo_class="interactive", verify_steps=4, spec_committed=12,
             drafts_offered=12, drafts_accepted=8),
        _Rec(slo_class="batch", verify_steps=2, spec_committed=2,
             drafts_offered=4, drafts_accepted=0),
        _Rec(slo_class="batch"),   # non-speculative record contributes 0
    ]
    s = speculation_stats(reqs, classes=("interactive", "batch", "standard"))
    assert s["spec_tokens_per_step"] == pytest.approx(14 / 6)
    assert s["spec_accept_rate"] == pytest.approx(8 / 16)
    assert s["spec_tokens_per_step_interactive"] == pytest.approx(3.0)
    assert s["spec_tokens_per_step_batch"] == pytest.approx(1.0)
    assert s["spec_tokens_per_step_standard"] is None
    # wired into the shared summary block
    full = latency_summary(reqs, classes=("interactive", "batch"))
    assert full["spec_tokens_per_step"] == s["spec_tokens_per_step"]


def test_speculation_stats_silent_without_speculation():
    from repro.serving.metrics import latency_summary, speculation_stats
    reqs = [_Rec(), _Rec(verify_steps=0, spec_committed=0)]
    assert speculation_stats(reqs) == {}
    assert not any(k.startswith("spec_") for k in latency_summary(reqs))


# ---------------------------------------------------------------------------
# Simulator: deterministic acceptance model
# ---------------------------------------------------------------------------
def _sim_profile():
    from repro.core.profiles import Profile
    from repro.core.strategy import StrategyConfig
    return Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                  value_bits=8, granularity="per_channel"),
                   cr=2.0, s_enc=5e8, s_dec=5e8)


def _sim_requests(n=40, seed=3):
    import numpy as np
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(0.05))
        out.append(Request(rid=i, workload="qalike", arrival=t,
                           ctx_tokens=int(rng.integers(200, 2000)),
                           out_tokens=int(rng.integers(20, 200)),
                           kv_bytes=float(rng.integers(1, 8)) * 1e6))
    return out


def _sim(cfg, needs_ctx=False):
    from repro.serving.network import BandwidthTrace, GBPS
    from repro.serving.simulator import Simulator, StaticPolicy
    pol = StaticPolicy(_sim_profile(), "u8")
    pol.needs_ctx = needs_ctx
    return Simulator(cfg, pol, BandwidthTrace.constant(2 * GBPS),
                     _sim_requests())


def test_sim_spec_k_zero_is_bit_identical():
    from repro.serving.simulator import SimConfig
    a = _sim(SimConfig(scenario="pd", n_prefill=2, n_decode=2, seed=0)).run()
    b = _sim(SimConfig(scenario="pd", n_prefill=2, n_decode=2, seed=0,
                       spec_k=0, spec_accept=0.9)).run()
    for x, y in zip(a.requests, b.requests):
        assert x.done == y.done and x.breakdown == y.breakdown


def test_sim_speculation_deterministic_and_sums_to_jct():
    from repro.serving.simulator import SimConfig, spec_tokens_per_step
    cfg = SimConfig(scenario="pd", n_prefill=2, n_decode=2, seed=0,
                    straggler_sigma=0.15, spec_k=4, spec_accept=0.6)
    r1, r2 = _sim(cfg).run(), _sim(cfg).run()
    for x, y in zip(r1.requests, r2.requests):
        assert x.done == y.done and x.breakdown == y.breakdown
    base = _sim(SimConfig(scenario="pd", n_prefill=2, n_decode=2, seed=0,
                          straggler_sigma=0.15)).run()
    for r in r1.requests:     # breakdown identity survives speculation
        assert sum(r.breakdown.values()) == pytest.approx(r.jct, abs=1e-9)
    dec = sum(r.breakdown["decode"] for r in r1.requests)
    dec0 = sum(r.breakdown["decode"] for r in base.requests)
    assert dec < dec0         # speculation shortens the decode stream
    # acceptance jitter is a pure hash of (seed, rid): no rng consumed
    tps = [spec_tokens_per_step(cfg, i) for i in range(50)]
    assert tps == [spec_tokens_per_step(cfg, i) for i in range(50)]
    assert all(1.0 <= t <= cfg.spec_k + 1 for t in tps)
    assert len(set(tps)) > 1  # requests genuinely differ


def test_sim_fast_pd_bit_parity_holds_with_speculation():
    from repro.serving.simulator import SimConfig
    cfg = SimConfig(scenario="pd", n_prefill=3, n_decode=2, seed=0,
                    straggler_sigma=0.15, spec_k=4, spec_accept=0.6)
    fast, slow = _sim(cfg), _sim(cfg, needs_ctx=True)
    assert fast._fast_pd_eligible() and not slow._fast_pd_eligible()
    rf, rs = fast.run(), slow.run()
    for a, b in zip(rf.requests, rs.requests):
        assert a.done == b.done and a.ttft == b.ttft
        assert a.breakdown == b.breakdown, a.rid


# ---------------------------------------------------------------------------
# Sanitizer: speculative rollback accounting (satellite 2)
# ---------------------------------------------------------------------------
def test_sanitizer_silent_on_legal_speculative_rollback():
    from repro.analysis import sanitize
    from repro.core.kvcache import PageTable
    assert not sanitize.enabled() or sanitize.uninstall() is None
    sanitize.install()
    try:
        pt = PageTable(num_pages=32, page_size=8)
        pt.ensure(0, 20)                       # 3 committed pages
        pt.ensure(0, 20 + 13)                  # +2 pages for 13 drafts
        freed = pt.release_tail(0, 21)         # rollback to 21 committed
        assert len(freed) == 2
        assert pt.release_tail(0, 21) == []    # idempotent re-rollback: ok
        pt.check()
        pt.release(0)
    finally:
        sanitize.uninstall()


def test_sanitizer_catches_double_released_rollback_page():
    from repro.analysis import sanitize
    from repro.core.kvcache import PageTable
    sanitize.install()
    try:
        pt = PageTable(num_pages=32, page_size=8)
        pt.ensure(0, 24)
        freed = pt.release_tail(0, 9)          # 2 tail pages to the pool
        # buggy rollback path: the slot still claims a page it freed
        pt.pages[0].append(freed[0])
        with pytest.raises(sanitize.SanitizerError) as ei:
            pt.release_tail(0, 9)
        assert ei.value.kind == "double-release"
    finally:
        sanitize.uninstall()


@pytest.mark.slow
def test_sanitizer_silent_under_paged_speculative_run(reference_model):
    """End-to-end: a paged speculative run under the installed sanitizer
    (ensure -> verify -> release_tail rollback every step) must complete
    with zero findings and drain clean."""
    from repro.analysis import sanitize
    sanitize.install()
    try:
        rt = build_runtime(reference_model, paged=True, spec_k=4)
        out = run_scenario(rt)
        _assert_width_one_exact(reference_model, rt, out,
                                _baseline(reference_model, "pool", True))
    finally:
        sanitize.uninstall()
