"""The restored hand-off's injection into an arena on the device.

Whatever the restore's dtype — fp16 (a view of an identity payload's
bytes) or float32 — K and V cross to the arena's device in one push, and
one jitted program per dtype casts them and writes every attention
layer's row, for every slot.  The arena it leaves is bit-equal, on every
leaf, to a numpy oracle computed on the host: the slot's rows set to the
payload rounded once to bf16, the state as sent, every other element as
it was.  Every arena takes an fp16 identity restore; the engine, the
store's re-compression and lossy restores get float32."""
from dataclasses import replace
from types import SimpleNamespace

import jax
import ml_dtypes
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.kvcache import STATE_DTYPES, KVCache
from repro.core.pipeline import CompressionPipeline
from repro.core.profiles import IDENTITY_PROFILE, Profile
from repro.core.strategy import BASELINES

SEQ, MAX_LEN, SLOTS, SLOT = 12, 16, 3, 2
CASES = ["gqa_prefix_scan", "jamba2-3b-reduced", "fp16_extremes"]


def _model(case: str):
    if case == "jamba2-3b-reduced":
        return get_config("jamba2-3b-reduced")
    # tiny-lm is GQA (4 heads over 2 KV heads); local/global layers give
    # one attention layer in the prefix, then two scanned blocks of two
    return replace(get_config("tiny-lm"), name="tiny-local-global",
                   num_layers=5, local_global_period=2, sliding_window=8)


def _arena(cfg, seed: int):
    """A dense arena whose every leaf holds random values, so rows the
    injection must not touch are not zeros."""
    from repro.models.transformer import init_cache

    caches = init_cache(cfg, SLOTS, MAX_LEN)
    leaves, tree = jax.tree_util.tree_flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [jax.random.normal(k, x.shape).astype(x.dtype)
               for k, x in zip(keys, leaves)])


def _handoff(cfg, arena, case: str, seed: int) -> KVCache:
    """A float32 KVCache of the arena's attention shape (and, for a
    hybrid, the SSM layers' state), holding fp16-exact values."""
    from repro.core.quality import _layers_of

    prefix, blocks, n_blocks = _layers_of(cfg, arena, "attn")
    leaf = (prefix + blocks)[0]["k"]
    shape = (len(prefix) + len(blocks) * n_blocks, leaf.shape[-2], SEQ,
             leaf.shape[-1])
    rng = np.random.default_rng(seed)
    kv = rng.standard_normal((2,) + shape) * 10.0 ** rng.uniform(
        -3, 3, (2,) + shape)
    if case == "fp16_extremes":
        tiny = np.finfo(np.float16).smallest_subnormal
        edges = np.array([65504.0, -65504.0, 65472.0, tiny, -tiny, 3 * tiny,
                          1023 * tiny, np.finfo(np.float16).tiny, 0.0, -0.0,
                          65519.0, 1.0 + 2.0 ** -10], np.float64)
        flat = kv.reshape(-1)
        reps = flat.size // (7 * edges.size)
        flat[: 7 * edges.size * reps: 7] = np.tile(edges, reps)
        # every subnormal magnitude of fp16 somewhere in the payload
        sub = np.arange(1, 1024) * tiny
        flat[1: 1 + 2 * sub.size: 2] = sub * np.where(
            np.arange(sub.size) % 2, -1, 1)
    kv = kv.astype(np.float16).astype(np.float32)
    state = None
    ssm_prefix, ssm_blocks, ssm_n = _layers_of(cfg, arena, "ssm")
    if ssm_prefix or ssm_blocks:
        n = len(ssm_prefix) + len(ssm_blocks) * ssm_n
        one = (ssm_prefix + ssm_blocks)[0]
        state = {t: rng.standard_normal((n,) + one[t].shape[-2:]).astype(
            STATE_DTYPES[t]) for t in ("ssm", "conv")}
    return KVCache(kv[0], kv[1], state)


def _bits(tree):
    return [np.asarray(x).view(np.uint8)
            for x in jax.tree_util.tree_leaves(tree)]


def _assert_bit_equal(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(_bits(got), _bits(want)):
        np.testing.assert_array_equal(g, w)


def _oracle(cfg, arena, kv: KVCache):
    """The arena, as numpy, after ``kv`` is written into row ``SLOT``:
    each attention layer's positions ``:SEQ`` hold its K and V rounded
    once to bf16, each SSM layer's state is as sent, and every other
    element is as it was.  Layers are in hand-off order: the prefix's,
    then each scanned block's in turn."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    out = jax.tree_util.tree_map(np.array, arena)
    layers = {"attn": [], "ssm": []}
    for i, spec in enumerate(plan.prefix_specs):
        layers.get(spec.kind, []).append(out["prefix"][f"layer{i}"])
    for b in range(plan.n_blocks):
        for j, spec in enumerate(plan.period_specs):
            leaves = out["blocks"][f"layer{j}"]
            layers.get(spec.kind, []).append(
                {t: a[b] for t, a in leaves.items()})   # views into out
    assert len(layers["attn"]) == kv.k.shape[0]
    for layer, k, v in zip(layers["attn"], kv.k, kv.v):
        for t, x in (("k", k), ("v", v)):
            layer[t][SLOT, :SEQ] = x.astype(ml_dtypes.bfloat16).swapaxes(0, 1)
    for i, layer in enumerate(layers["ssm"]):
        for t in ("ssm", "conv"):
            layer[t][SLOT] = kv.state[t][i]
    return out


@pytest.mark.parametrize("case", CASES)
def test_device_injection_is_bit_equal_to_the_host_path(case):
    """Both restores of an identity payload, fp16 and float32, leave the
    arena the host's numpy oracle (:func:`_oracle`) computes, bit for
    bit."""
    from repro.core.quality import inject_kv

    cfg = _model(case)
    arena = _arena(cfg, seed=1)
    kv = _handoff(cfg, arena, case, seed=2)
    pipe = CompressionPipeline(IDENTITY_PROFILE.strategy)
    comp = pipe.compress(kv)
    wide, narrow = pipe.decompress(comp), pipe.decompress(comp, fp16=True)
    assert wide.k.dtype == np.float32 and narrow.k.dtype == np.float16
    # the fp16 restore is a view of the payload's bytes, not a copy
    assert not narrow.k.flags.owndata and not narrow.v.flags.owndata
    assert np.shares_memory(narrow.k, np.frombuffer(comp.identity_payload,
                                                    np.float16))
    want = _oracle(cfg, arena, kv)
    # the oracle moved the slot's rows, and nothing of the arena it read
    assert any(not np.array_equal(w, a) for w, a in zip(_bits(want),
                                                         _bits(arena)))
    for restore in (narrow, wide):
        _assert_bit_equal(inject_kv(cfg, arena, SLOT, restore), want)
    if case == "fp16_extremes":
        assert np.isin(np.float16(65504.0), narrow.k)
        assert (np.abs(narrow.k) < np.finfo(np.float16).tiny).sum() > 1000


def _decode_worker(cfg, n_slots: int, paged: bool = False):
    from repro.serving.workers import DecodeWorker, ModelHandle, RuntimeConfig

    rc = RuntimeConfig(seq=SEQ, decode_tokens=MAX_LEN - SEQ - 2, mode="pd",
                       paged=paged, page_size=4)
    assert rc.arena_max_len == MAX_LEN
    return DecodeWorker(0, ModelHandle(cfg, None), rc, n_slots, store=None)


def test_one_program_serves_every_slot_and_the_span_says_where_it_cast(
        tmp_path):
    """One compiled program per restore dtype serves every slot: four
    fp16 restores compile once, and two float32 restores (the harness's
    warm-up hand-off, a lossy restore) once more; the ``inject`` span
    counts the bytes written and no compile after each dtype's first."""
    from repro.core.quality import DeviceKV, _scatter_kv
    from repro.serving import tracing

    cfg = _model("gqa_prefix_scan")
    dw = _decode_worker(cfg, n_slots=4)
    # jit keys a program on whether its arguments are committed to a
    # device; a serving arena is from its first write on, so commit it
    dw._arena = jax.device_put(dw.ensure_arena(), jax.devices()[0])
    pipe = CompressionPipeline(IDENTITY_PROFILE.strategy)
    sent = [_handoff(cfg, dw._arena, "gqa_prefix_scan", seed=10 + s)
            for s in range(6)]
    comps = [pipe.compress(kv) for kv in sent]
    _scatter_kv.clear_cache()
    tracing.SPANS.clear()
    with jax.profiler.trace(str(tmp_path)):
        for s in range(4):
            dw.inject_restored(pipe.decompress(comps[s], fp16=True), s)
        compiled = _scatter_kv._cache_size()
        for s, i in ((1, 4), (3, 5)):
            dw.inject_restored(pipe.decompress(comps[i]), s)
        jax.block_until_ready(dw._arena)
    spans = [s for s in tracing.SPANS if s.name == "inject"]
    tracing.SPANS.clear()
    assert compiled == 1 and _scatter_kv._cache_size() == 2
    assert len(spans) == 6
    assert all("compiles" not in s.counters for s in spans[1:4] + spans[5:]),\
        [s.counters for s in spans]
    assert all("device_cast" not in s.counters for s in spans)
    n_kv = sent[0].k.size + sent[0].v.size
    assert all(s.counters["bytes"] == 2 * n_kv for s in spans)
    for s, kv in zip((0, 1, 2, 3), (sent[0], sent[4], sent[2], sent[5])):
        back = DeviceKV(cfg, dw._arena, s, SEQ).host()
        for got, want in ((back.k, kv.k), (back.v, kv.v)):
            np.testing.assert_array_equal(
                got, want.astype(ml_dtypes.bfloat16).astype(np.float32))


def _tiny_lm():
    from repro.models import init_params

    cfg = get_config("tiny-lm")
    params, _ = init_params(cfg, seed=3)
    return cfg, params


KIVI = Profile(BASELINES["kivi"], cr=4.0, s_enc=1e8, s_dec=1e8)
CONSUMERS = ["engine", "recompress", "paged_fetch", "pd_paged", "lossy",
             "dense_fetch", "pd_dense"]
ARENAS = {"paged_fetch", "pd_paged", "dense_fetch", "pd_dense"}


def _identity_entry(cfg):
    cache = _arena(cfg, seed=4)
    kv = _handoff(cfg, cache, "gqa_prefix_scan", seed=5)
    comp = CompressionPipeline(IDENTITY_PROFILE.strategy).compress(kv)
    return SimpleNamespace(payload=(comp, 7, 1e9), kv_bytes=kv.nbytes_wire(),
                           wire_bytes=comp.total_bytes())


def _drive(consumer: str, monkeypatch):
    """Run ``consumer`` with every restore it makes recorded: the dtypes
    of the K and V each restore returned, by strategy."""
    import repro.serving.cluster as C
    import repro.serving.engine as E
    from repro.core.strategy import is_identity
    from repro.serving import BandwidthTrace, GBPS, SchedulerConfig
    from repro.serving.workers import recompress_entry, RuntimeConfig

    seen = []
    decompress = CompressionPipeline.decompress

    def recorded(self, comp, *args, **kwargs):
        kv = decompress(self, comp, *args, **kwargs)
        seen.append((is_identity(comp.strategy), kv.k.dtype, kv.v.dtype))
        return kv

    monkeypatch.setattr(CompressionPipeline, "decompress", recorded)
    cfg, params = _tiny_lm()
    if consumer == "engine":
        monkeypatch.setattr(E, "get_reference_model", lambda: (cfg, params))
        eng = E.DisaggregatedEngine(static_profile=IDENTITY_PROFILE, seq=16,
                                    decode_tokens=2, batch=1)
        eng.serve("qalike", BandwidthTrace.constant(1 * GBPS))
    elif consumer == "recompress":
        assert recompress_entry(_identity_entry(cfg), KIVI) is not None
    elif consumer in ("paged_fetch", "dense_fetch"):
        dw = _decode_worker(cfg, 2, paged=consumer == "paged_fetch")
        entry = _identity_entry(cfg)
        assert dw.fetch_entry(entry, 1)[0] == 7
    elif consumer in ("pd_paged", "pd_dense"):
        monkeypatch.setattr(C, "get_reference_model", lambda: (cfg, params))
        rt = C.ClusterRuntime(
            static_profile=IDENTITY_PROFILE,
            config=RuntimeConfig(seq=16, decode_tokens=3, mode="pd",
                                 paged=consumer == "pd_paged", page_size=8,
                                 pd_inject_restored=True),
            scheduler=SchedulerConfig(max_slots=2, max_prefills_per_step=2))
        for s in (1, 2):
            rt.submit("qalike", prompt_seed=s)
        rt.run()
        assert len(rt.completed) == 2
    else:   # a lossy strategy's restore asked for fp16 is float32 as ever
        kv = _handoff(cfg, _arena(cfg, seed=4), "gqa_prefix_scan", seed=6)
        pipe = CompressionPipeline(KIVI.strategy)
        comp = pipe.compress(kv)
        got, want = pipe.decompress(comp, fp16=True), pipe.decompress(comp)
        np.testing.assert_array_equal(got.k, want.k)
        np.testing.assert_array_equal(got.v, want.v)
    return seen


@pytest.mark.parametrize("consumer", CONSUMERS)
def test_only_the_dense_arena_takes_an_fp16_restore(consumer, monkeypatch):
    """Every arena, dense or paged, takes an fp16 identity restore; the
    engine, the store's re-compression and lossy restores get float32."""
    seen = _drive(consumer, monkeypatch)
    identity = [dt for ident, *dt in seen if ident]
    lossy = [dt for ident, *dt in seen if not ident]
    if consumer == "lossy":
        assert not identity and len(lossy) == 2
    else:
        assert identity, consumer
    assert all(dt == [np.float32] * 2 for dt in lossy), lossy
    want = np.float16 if consumer in ARENAS else np.float32
    assert all(dt == [want] * 2 for dt in identity), (consumer, identity)
