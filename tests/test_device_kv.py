"""The prompt's KV taken off the device in one gather (``DeviceKV``): the
identity hand-off's fp16 payload made on the device is byte for byte the
host path's, a lossy strategy restores what it restored from the host
``KVCache``, and ``extract_kv`` keeps its float32 values.  The reference
is the per-layer numpy pull the serving path used before the gather."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.kvcache import KVCache
from repro.core.pipeline import CompressionPipeline
from repro.core.profiles import IDENTITY_PROFILE, Profile
from repro.core.strategy import BASELINES

SEQ, MAX_LEN = 24, 30


PLANS = ["blocks", "ssm_hybrid", "local_global"]


def _model(plan: str):
    tiny = get_config("tiny-lm")
    if plan == "blocks":  # 4 attention layers, all scanned
        return tiny
    if plan == "ssm_hybrid":
        # A S A S A: one attention layer in the prefix, then two scanned
        # (S, A) blocks; the SSM layers hold no KV
        return replace(tiny, name="tiny-hybrid", family="hybrid",
                       num_layers=5, ssm=True, attn_period=2, attn_offset=0)
    # local, global, local, global, local: one attention layer in the
    # prefix, then two scanned blocks of two attention layers each
    return replace(tiny, name="tiny-local-global", num_layers=5,
                   local_global_period=2, sliding_window=8)


def _caches(cfg, batch: int, seed: int):
    """A cache pytree whose attention leaves hold bf16 values of 1e-9 to
    1e4 in magnitude: fp16 subnormals and underflow included."""
    from repro.models.transformer import init_cache

    caches = init_cache(cfg, batch, MAX_LEN)
    leaves, tree = jax.tree_util.tree_flatten_with_path(caches)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for (path, leaf), key in zip(leaves, keys):
        if path[-1].key in ("k", "v"):
            a, b = jax.random.split(key)
            mag = 10.0 ** jax.random.uniform(a, leaf.shape, minval=-9.0,
                                             maxval=4.0)
            leaf = (jax.random.normal(b, leaf.shape) * mag).astype(leaf.dtype)
        else:               # an SSM layer's scan or conv state
            leaf = jax.random.normal(key, leaf.shape).astype(leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


def _extract_kv_as_before(cfg, caches, batch_idx: int, upto: int) -> KVCache:
    """One device->host pull per layer and tensor, widened to float32 and
    stacked on the host; an SSM layer's scan and conv state pulled as
    held, in layer order."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    ks, vs = [], []
    state = {"ssm": [], "conv": []}

    def pull(c, row):
        if "k" not in c:
            for t in state:
                state[t].append(np.asarray(c[t][row]))
            return
        ks.append(np.asarray(c["k"][row][:upto],
                             np.float32).transpose(1, 0, 2))
        vs.append(np.asarray(c["v"][row][:upto],
                             np.float32).transpose(1, 0, 2))

    for i in range(len(plan.prefix_specs)):
        pull(caches["prefix"][f"layer{i}"], batch_idx)
    for blk in range(plan.n_blocks):
        for j in range(len(plan.period_specs)):
            pull(caches["blocks"][f"layer{j}"], (blk, batch_idx))
    return KVCache(np.stack(ks), np.stack(vs),
                   {t: np.stack(a) for t, a in state.items()}
                   if state["ssm"] else None)


def _worker(cfg, profile: Profile):
    from repro.serving.workers import ModelHandle, PrefillWorker, RuntimeConfig

    return PrefillWorker(0, ModelHandle(cfg, None),
                         RuntimeConfig(seq=SEQ, mode="pd"),
                         static_profile=profile)


def _request():
    from repro.serving.request import Request

    return Request(rid=0, workload="qalike", arrival=0.0, ctx_tokens=SEQ,
                   out_tokens=4, kv_bytes=0.0)


@pytest.mark.parametrize("plan", PLANS)
def test_plans_under_test_hold_what_they_claim(plan):
    from repro.models.transformer import plan_stack

    p = plan_stack(_model(plan))
    attn_prefix = [s for s in p.prefix_specs if s.kind == "attn"]
    attn_period = [s for s in p.period_specs if s.kind == "attn"]
    assert p.n_blocks >= 2
    assert len(attn_prefix) == (0 if plan == "blocks" else 1)
    assert len(attn_period) == (2 if plan == "local_global" else 1)
    if plan == "ssm_hybrid":
        assert any(s.kind != "attn" for s in p.prefix_specs + p.period_specs)


@pytest.mark.parametrize("plan", PLANS)
@pytest.mark.parametrize("strategy", ["identity", "kivi"])
def test_select_and_compress_matches_the_host_path(plan, strategy):
    cfg = _model(plan)
    caches = _caches(cfg, batch=1, seed=3)
    profile = (IDENTITY_PROFILE if strategy == "identity" else
               Profile(BASELINES[strategy], cr=8.0, s_enc=1e8, s_dec=1e8))
    comp, ctx, _, used, _ = _worker(cfg, profile).select_and_compress(
        _request(), caches, 0.0, bandwidth=1e9, slo_default="ttft")
    ref_kv = _extract_kv_as_before(cfg, caches, 0, SEQ)
    pipe = CompressionPipeline(profile.strategy)
    ref = pipe.compress(ref_kv)
    assert used is profile
    assert ctx.kv_bytes == ref_kv.nbytes_wire()
    assert comp.shape == ref.shape == ref_kv.shape
    assert comp.total_bytes() == ref.total_bytes()
    if strategy == "identity":
        assert isinstance(comp.identity_payload, bytes)
        assert comp.identity_payload == ref.identity_payload
    got, want = pipe.decompress(comp), pipe.decompress(ref)
    np.testing.assert_array_equal(got.k, want.k)
    np.testing.assert_array_equal(got.v, want.v)
    _assert_same_state(got, want)


def _assert_same_state(got: KVCache, want: KVCache):
    assert (got.state is None) == (want.state is None)
    for t in (want.state or {}):
        assert got.state[t].dtype == want.state[t].dtype
        np.testing.assert_array_equal(got.state[t], want.state[t])


@pytest.mark.parametrize("plan", PLANS)
def test_extract_kv_keeps_its_float32_values(plan):
    from repro.core.quality import extract_kv

    cfg = _model(plan)
    caches = _caches(cfg, batch=3, seed=5)
    for b in (0, 2):
        got = extract_kv(cfg, caches, b, upto=SEQ)
        want = _extract_kv_as_before(cfg, caches, b, SEQ)
        assert got.k.dtype == got.v.dtype == np.float32
        np.testing.assert_array_equal(got.k, want.k)
        np.testing.assert_array_equal(got.v, want.v)
        _assert_same_state(got, want)


def test_the_fp16_payload_is_c_ordered_k_then_v():
    from repro.core.quality import DeviceKV

    cfg = _model("local_global")
    caches = _caches(cfg, batch=2, seed=7)
    src = DeviceKV(cfg, caches, 1, SEQ)
    arr = src.fp16()
    want = _extract_kv_as_before(cfg, caches, 1, SEQ)
    assert arr.dtype == np.float16 and arr.flags.c_contiguous
    assert arr.shape == (2,) + want.shape == (2,) + src.shape
    np.testing.assert_array_equal(arr[0], want.k.astype(np.float16))
    np.testing.assert_array_equal(arr[1], want.v.astype(np.float16))
    assert src.nbytes_wire() == want.nbytes_wire() == arr.nbytes
    assert src.cache_dtype == jnp.bfloat16
