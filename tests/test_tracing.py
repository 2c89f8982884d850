"""The serving path's spans and counters (``repro.serving.tracing``):
nothing is kept without a profiler session; under one, every cold PD
request leaves its stages, tied to its rid and to the iteration that ran
them, with the clock's own seconds."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.profiles import Profile
from repro.core.strategy import StrategyConfig
from repro.serving import BandwidthTrace, GBPS, SchedulerConfig, tracing

COLD_STAGES = ("prefill", "kv_pull", "select", "compress", "restore",
               "inject")


def _runtime(reference_model):
    from repro.serving.cluster import ClusterRuntime
    from repro.serving.workers import RuntimeConfig

    profile = Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                     value_bits=8, granularity="per_channel"),
                      cr=2.0, s_enc=5e8, s_dec=5e8)
    rt = ClusterRuntime(
        static_profile=profile,
        config=RuntimeConfig(seq=64, decode_tokens=4, mode="pd",
                             prefill_tok_s=None, decode_tok_s=None,
                             pd_inject_restored=True),
        trace=BandwidthTrace.constant(1 * GBPS),
        scheduler=SchedulerConfig(max_slots=4, max_prefills_per_step=2,
                                  max_queue=16))
    rt.model_cfg, rt.params = reference_model
    return rt


def _serve(rt, seeds=(11, 12, 13)):
    for s in seeds:
        rt.submit("qalike", prompt_seed=s)
    rt.run()
    return {r.rid: r for r in rt.completed}


def _ancestors(sp):
    out = []
    while sp.parent is not None:
        sp = sp.parent
        out.append(sp.name)
    return out


@pytest.fixture
def traced_run(reference_model, tmp_path):
    tracing.SPANS.clear()
    rt = _runtime(reference_model)
    with jax.profiler.trace(str(tmp_path)):
        done = _serve(rt)
    spans = list(tracing.SPANS)
    tracing.SPANS.clear()
    return rt, done, spans


@pytest.mark.slow
def test_no_span_without_a_profiler(reference_model):
    tracing.SPANS.clear()
    done = _serve(_runtime(reference_model))
    assert len(done) == 3
    assert len(tracing.SPANS) == 0
    assert tracing.span("step") is tracing._OFF
    assert not tracing.span("step")


@pytest.mark.slow
def test_each_cold_request_leaves_its_stages(traced_run):
    _, done, spans = traced_run
    assert len(done) == 3
    for name in COLD_STAGES:
        got = sorted(s.rid for s in spans if s.name == name)
        assert got == sorted(done), (name, got)
    for s in spans:
        if s.name in COLD_STAGES:
            assert "step" in _ancestors(s), (s.name, _ancestors(s))
            assert s.t1 >= s.t0
    decode = [s for s in spans if s.name == "decode_step"]
    assert decode and all(s.counters["live"] >= 1 for s in decode)
    assert all(s.parent.name == "decode_step"
               for s in spans if s.name == "token_pull")
    assert {s.rid for s in spans if s.name == "finish"} == set(done)


@pytest.mark.slow
def test_kv_pull_counts_host_bytes_and_transfers(traced_run):
    rt, _, spans = traced_run
    m = rt.model_cfg
    pulls = [s for s in spans if s.name == "kv_pull"]
    host = 2 * m.num_layers * m.kv_heads * 64 * m.resolved_head_dim * 4
    for s in pulls:
        assert s.counters["bytes"] == host
        assert s.counters["transfers"] == 2 * m.num_layers


@pytest.mark.slow
def test_codec_spans_are_the_cluster_clock(traced_run):
    _, done, spans = traced_run
    for name, key in (("compress", "compress"), ("restore", "decompress")):
        by_rid = {s.rid: s.seconds for s in spans if s.name == name}
        assert by_rid == {rid: r.breakdown[key] for rid, r in done.items()}


def test_kv_pull_bytes_match_the_extracted_cache(reference_model, tmp_path):
    from repro.core.quality import _jitted_steps, _prompts_for, extract_kv

    cfg, params = reference_model
    pre, _, _ = _jitted_steps(cfg.name, 32, 1, 40)
    toks, _ = _prompts_for("qalike", 1, 32, 0)
    _, caches = pre(params, {"tokens": jnp.asarray(toks)})
    tracing.SPANS.clear()
    with jax.profiler.trace(str(tmp_path)):
        kv = extract_kv(cfg, caches, 0, upto=32)
    (sp,) = tracing.SPANS
    tracing.SPANS.clear()
    assert sp.name == "kv_pull" and sp.parent is None and sp.rid is None
    assert sp.counters["bytes"] == kv.k.nbytes + kv.v.nbytes
    assert sp.counters["transfers"] == 2 * kv.num_layers


def test_compiles_land_on_the_innermost_span(tmp_path):
    tracing.SPANS.clear()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("outer", rid=7):
            with tracing.timed("inner") as sp:
                jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
    inner, outer = list(tracing.SPANS)
    tracing.SPANS.clear()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner is sp and inner.parent is outer and inner.rid == 7
    assert inner.counters.get("compiles", 0) >= 1
    assert "compiles" not in outer.counters
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_timed_without_a_profiler_keeps_only_its_clock():
    tracing.SPANS.clear()
    with tracing.timed("compress") as sp:
        pass
    assert not sp and sp.seconds >= 0.0
    sp.add(bytes=1)
    assert len(tracing.SPANS) == 0
