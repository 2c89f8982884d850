"""Paged KV arena (ISSUE 7): page-table invariants, fp16-page token
parity vs the pinned PR-1 fixture, and the quantized-resident fast path.
"""
import json

import numpy as np
import pytest

from repro.core.kvcache import ArenaOutOfPages, PageTable
from repro.core.profiles import Profile
from repro.core.strategy import StrategyConfig, paged_eligible
from repro.serving import BandwidthTrace, GBPS, SchedulerConfig


# ---------------------------------------------------------------------------
# PageTable: pure host-side bookkeeping
# ---------------------------------------------------------------------------
def test_page_table_conservation_under_churn():
    """Random admit/grow/release churn: every step preserves page
    conservation, single ownership, and the scratch-page reservation."""
    rng = np.random.default_rng(0)
    pt = PageTable(num_pages=33, page_size=8)
    live = set()
    for _ in range(500):
        slot = int(rng.integers(0, 10))
        if slot in live and rng.random() < 0.4:
            assert pt.release(slot) > 0
            live.discard(slot)
        else:
            try:
                pt.ensure(slot, int(rng.integers(1, 65)))
                live.add(slot)
            except ArenaOutOfPages:
                pass    # pool full: the ask must leave state untouched
        pt.check()
    for slot in list(live):
        pt.release(slot)
    pt.check()
    assert pt.free_pages == 32      # everything back, page 0 still reserved


def test_page_table_no_partial_grant():
    pt = PageTable(num_pages=5, page_size=4)    # 4 allocatable pages
    pt.ensure(0, 8)                             # slot 0 takes 2
    owned_before, free_before = list(pt.pages[0]), pt.free_pages
    with pytest.raises(ArenaOutOfPages):
        pt.ensure(1, 100)
    assert pt.pages.get(1, []) == []            # nothing granted
    assert pt.free_pages == free_before
    assert pt.pages[0] == owned_before
    pt.check()


def test_page_table_block_row_scratch_padding():
    pt = PageTable(num_pages=9, page_size=8)
    pt.ensure(2, 20)                            # ceil(20/8) = 3 pages
    row = pt.block_row(2, 5)
    assert row.dtype == np.int32 and row.shape == (5,)
    assert (row[:3] > 0).all()                  # real pages
    assert (row[3:] == 0).all()                 # scratch sentinel padding
    # growth is monotone: ensure() at a smaller ask allocates nothing
    assert pt.ensure(2, 8) == []


def test_page_table_byte_accounting():
    fp16 = PageTable.page_bytes_fp16(16, 2, 32, 4)
    q4 = PageTable.page_bytes_quant(16, 2, 32, 4, bits=4, group=32)
    q8 = PageTable.page_bytes_quant(16, 2, 32, 4, bits=8, group=32)
    assert fp16 > q8 > q4 > 0
    # int4 with coarse groups approaches 4x over fp16
    assert fp16 / q4 > 3.0 and fp16 / q8 > 1.5


# ---------------------------------------------------------------------------
# Runtime parity: the paged arena must be a pure re-layout
# ---------------------------------------------------------------------------
def _paged_cfg(mode, **kw):
    from repro.serving.engine import RuntimeConfig
    # page_size=8 divides max_len = seq + decode_tokens + 2 = 72, so the
    # paged gathered view is shape- and value-identical to the dense
    # arena and parity is bit-exact.
    return RuntimeConfig(seq=64, decode_tokens=6, prefill_tok_s=2000.0,
                         decode_tok_s=500.0, mode=mode, paged=True,
                         page_size=8, **kw)


def _runtime(reference_model, config, profile=None):
    from repro.serving.engine import ServingRuntime
    if profile is None:
        profile = Profile(
            StrategyConfig(quantizer="uniform", key_bits=8, value_bits=8,
                           granularity="per_channel"),
            cr=2.0, s_enc=5e8, s_dec=5e8)
    rt = ServingRuntime(
        static_profile=profile, config=config,
        trace=BandwidthTrace.constant(1 * GBPS),
        scheduler=SchedulerConfig(max_slots=6, max_prefills_per_step=2,
                                  max_queue=32))
    rt.model_cfg, rt.params = reference_model
    return rt


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["pool", "pd"])
def test_paged_fp16_token_parity_with_pr1_fixture(reference_model, mode):
    """The fixture profile (per-channel, asymmetric) is NOT
    paged-eligible, so its pool hits take the materialized fp16-page
    injection path — which must reproduce the pinned PR-1 tokens
    bit-for-bit in both pool and pd modes."""
    from _runtime_scenario import FIXTURE, params_digest, run_scenario
    fix = json.loads(FIXTURE.read_text())
    rt = _runtime(reference_model, _paged_cfg(mode))
    if params_digest(rt.params) != fix["params_digest"]:
        pytest.skip("reference model differs from the fixture's "
                    "(e.g. CI trains a smaller REPRO_REF_STEPS model)")
    out = run_scenario(rt)
    assert set(out) == set(fix["outputs"])
    for rid, rec in fix["outputs"].items():
        assert out[rid]["pool_hit"] == rec["pool_hit"], rid
        assert out[rid]["tokens"] == rec["tokens"], rid
    # all pages returned to the pool after the run, invariants intact
    for dw in rt.decode_workers:
        dw.layout.page_table.check()
        assert (dw.layout.page_table.free_pages
                == dw.layout.page_table.num_pages - 1)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["pool", "pd"])
def test_paged_vs_dense_token_parity(reference_model, mode):
    """Fixture-independent twin of the parity test above: whatever the
    trained reference model is, the paged runtime must emit exactly the
    dense runtime's tokens across the hit/miss scenario."""
    from _runtime_scenario import run_scenario
    from repro.serving.engine import RuntimeConfig

    dense_cfg = RuntimeConfig(seq=64, decode_tokens=6, prefill_tok_s=2000.0,
                              decode_tok_s=500.0, mode=mode)
    out_dense = run_scenario(_runtime(reference_model, dense_cfg))
    out_paged = run_scenario(_runtime(reference_model, _paged_cfg(mode)))
    assert set(out_dense) == set(out_paged)
    for rid in out_dense:
        assert out_paged[rid]["pool_hit"] == out_dense[rid]["pool_hit"], rid
        assert out_paged[rid]["tokens"] == out_dense[rid]["tokens"], rid


@pytest.mark.slow
def test_paged_quant_resident_token_parity(reference_model):
    """A paged-eligible profile (per-token symmetric int8) keeps pool
    hits resident as quantized pages: tokens must match the dense
    (materialized-decompress) runtime exactly, and the hit's decompress
    term leaves the TTFT breakdown — with breakdowns still summing to
    JCT."""
    from _runtime_scenario import run_scenario
    from repro.serving.engine import RuntimeConfig

    profile = Profile(
        StrategyConfig(quantizer="uniform", key_bits=8, value_bits=8,
                       granularity="per_token", symmetric=True,
                       group_size=32),
        cr=2.0, s_enc=5e8, s_dec=5e8)
    assert paged_eligible(profile.strategy)

    dense_cfg = RuntimeConfig(seq=64, decode_tokens=6, prefill_tok_s=2000.0,
                              decode_tok_s=500.0)
    rt_dense = _runtime(reference_model, dense_cfg, profile=profile)
    rt_paged = _runtime(reference_model, _paged_cfg("pool"), profile=profile)
    out_dense, out_paged = run_scenario(rt_dense), run_scenario(rt_paged)

    assert set(out_dense) == set(out_paged)
    for rid in out_dense:
        assert out_paged[rid]["pool_hit"] == out_dense[rid]["pool_hit"], rid
        assert out_paged[rid]["tokens"] == out_dense[rid]["tokens"], rid

    hits_d = [r for r in rt_dense.completed if r.pool_hit]
    hits_p = [r for r in rt_paged.completed if r.pool_hit]
    assert len(hits_p) == len(hits_d) > 0
    for r in hits_d:    # dense hits pay the materialized decompress
        assert r.breakdown["decompress"] > 0
    for r in hits_p:    # paged hits decode the pages in the fused path
        assert r.breakdown["decompress"] == 0.0
    for r in rt_paged.completed:
        assert sum(r.breakdown.values()) == pytest.approx(r.jct, abs=1e-9)


@pytest.mark.slow
def test_paged_arena_pages_override_raises_when_exhausted(reference_model):
    """An explicit undersized ``arena_pages`` surfaces as
    ArenaOutOfPages instead of silently corrupting a stolen page."""
    rt = _runtime(reference_model, _paged_cfg("pool", arena_pages=4))
    rt.submit("qalike", prompt_seed=0)
    with pytest.raises(ArenaOutOfPages):
        rt.run()
