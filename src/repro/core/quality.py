"""Quality proxy: a trained tiny byte-LM measures the workload-dependent
accuracy impact of each compression strategy (DESIGN.md §8).

``evaluate_quality(strategy)`` returns per-workload *relative accuracy* —
greedy-decode token agreement against the uncompressed-KV decode, the
laptop-scale analogue of the paper's "97% relative accuracy" metric.  The
four synthetic workloads have genuinely different byte statistics, so KV
compressibility and accuracy rankings differ per workload (Motivation 1).
"""
from __future__ import annotations

import os
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.kvcache import KVCache
from repro.core.pipeline import CompressionPipeline
from repro.core.strategy import StrategyConfig, is_identity
from repro.data.synthetic import WORKLOADS, make_batch, make_prompt
from repro.data.tokenizer import ByteTokenizer

CACHE_DIR = Path(os.environ.get("REPRO_CACHE_DIR",
                                Path.home() / ".cache" / "repro"))
REF_STEPS = int(os.environ.get("REPRO_REF_STEPS", "400"))


# ---------------------------------------------------------------------------
# Reference model (trained once, cached to disk)
# ---------------------------------------------------------------------------
def _params_path(steps: int) -> Path:
    return CACHE_DIR / f"tiny_lm_s{steps}.npz"


def train_reference_model(steps: int = REF_STEPS, seed: int = 0,
                          batch: int = 16, seq: int = 256,
                          log_every: int = 0):
    """Train tiny-lm on the mixed workload soup; returns (cfg, params)."""
    from repro.distribution.optimizer import OptConfig, init_opt_state
    from repro.distribution.steps import make_train_step
    from repro.models import init_params

    cfg = get_config("tiny-lm")
    params, _ = init_params(cfg, seed=seed)
    oc = OptConfig(lr=3e-3, warmup_steps=max(steps // 10, 10),
                   total_steps=steps, schedule="cosine", weight_decay=0.01)
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, oc, remat=False))
    loss = None
    for i in range(steps):
        tokens, mask = make_batch("mixed", batch, seq, seed=seed * 100003 + i)
        b = {"tokens": jnp.asarray(tokens), "mask": jnp.asarray(mask[:, 1:])}
        params, opt_state, metrics = step_fn(params, opt_state, b)
        if log_every and (i + 1) % log_every == 0:
            print(f"step {i+1}/{steps} loss={float(metrics['loss']):.3f}")
        loss = metrics["loss"]
    return cfg, params, float(loss)


def get_reference_model(steps: int = REF_STEPS, seed: int = 0):
    """Load the cached reference model, training it on first use."""
    from repro.models import init_params

    cfg = get_config("tiny-lm")
    path = _params_path(steps)
    template, _ = init_params(cfg, seed=seed)
    leaves, treedef = jax.tree_util.tree_flatten(template)
    if path.exists():
        data = np.load(path)
        loaded = [jnp.asarray(data[f"arr_{i}"]) for i in range(len(leaves))]
        return cfg, jax.tree_util.tree_unflatten(treedef, loaded)
    cfg, params, _ = train_reference_model(steps=steps, seed=seed)
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    flat, _ = jax.tree_util.tree_flatten(params)
    np.savez(path, **{f"arr_{i}": np.asarray(x) for i, x in enumerate(flat)})
    return cfg, params


# ---------------------------------------------------------------------------
# Cache <-> KVCache conversion (attention layers, dense stacks)
# ---------------------------------------------------------------------------
def extract_kv(cfg, caches, batch_idx: int, upto: int) -> KVCache:
    """Pull one batch element's attention KV as (L, H, S, D) numpy: one
    device->host pull per layer and tensor (span ``kv_pull``)."""
    from repro.models.transformer import plan_stack
    from repro.serving import tracing

    plan = plan_stack(cfg)
    ks: List[np.ndarray] = []
    vs: List[np.ndarray] = []
    with tracing.span("kv_pull") as sp:
        for i, spec in enumerate(plan.prefix_specs):
            if spec.kind != "attn":
                continue
            c = caches["prefix"][f"layer{i}"]
            ks.append(np.asarray(c["k"][batch_idx, :upto],
                                 np.float32).transpose(1, 0, 2))
            vs.append(np.asarray(c["v"][batch_idx, :upto],
                                 np.float32).transpose(1, 0, 2))
        for blk in range(plan.n_blocks):
            for j, spec in enumerate(plan.period_specs):
                if spec.kind != "attn":
                    continue
                c = caches["blocks"][f"layer{j}"]
                ks.append(np.asarray(c["k"][blk, batch_idx, :upto],
                                     np.float32).transpose(1, 0, 2))
                vs.append(np.asarray(c["v"][blk, batch_idx, :upto],
                                     np.float32).transpose(1, 0, 2))
        kv = KVCache(np.stack(ks), np.stack(vs))
        if sp:
            sp.add(bytes=kv.k.nbytes + kv.v.nbytes,
                   transfers=len(ks) + len(vs))
    return kv


def copy_cache_slot(cfg, dst, src, slot, src_idx: int = 0):
    """Write one batch row of the ``src`` cache pytree into row ``slot`` of
    the (larger-batch) ``dst`` arena pytree — how a fresh batch-1 prefill
    lands in its slot.  Jitted once; ``slot`` is a traced scalar so slot
    recycling never recompiles."""
    if "self" in dst:
        raise NotImplementedError("slot arena: decoder-only caches")
    return _slot_copy(dst, src, jnp.asarray(slot, jnp.int32),
                      jnp.asarray(src_idx, jnp.int32))


@jax.jit
def _slot_copy(dst, src, slot, src_idx):
    def _write(batch_axis):
        def w(d, s):
            row = jax.lax.dynamic_slice_in_dim(s, src_idx, 1, batch_axis)
            start = [0] * d.ndim
            start[batch_axis] = slot
            return jax.lax.dynamic_update_slice(
                d, row.astype(d.dtype), tuple(start))
        return w

    # prefix leaves carry batch at axis 0, scanned blocks at axis 1
    return {
        "prefix": jax.tree_util.tree_map(_write(0), dst["prefix"],
                                         src["prefix"]),
        "blocks": jax.tree_util.tree_map(_write(1), dst["blocks"],
                                         src["blocks"]),
    }


def _put_like(buf, arr):
    """Host array ``arr`` on the device that holds ``buf``, in its dtype
    (cast on the host, so only ``buf.dtype`` bytes cross)."""
    return jax.device_put(np.asarray(arr, buf.dtype), buf.sharding)


def inject_kv(cfg, caches, batch_idx: int, kv: KVCache):
    """Write a (possibly lossy) KVCache back into the cache pytree, on the
    device that holds it."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    upto = kv.seq
    li = 0

    def _store(buf, arr):
        # arr (H, S, D) -> (S, H, D)
        return buf.at[batch_idx, :upto].set(
            _put_like(buf, arr.transpose(1, 0, 2)))

    new_prefix = {}
    for i, spec in enumerate(plan.prefix_specs):
        name = f"layer{i}"
        c = caches["prefix"][name]
        if spec.kind != "attn":
            new_prefix[name] = c
            continue
        new_prefix[name] = {"k": _store(c["k"], kv.k[li]),
                            "v": _store(c["v"], kv.v[li])}
        li += 1
    new_blocks = dict(caches["blocks"])
    attn_per_period = len([s for s in plan.period_specs if s.kind == "attn"])
    for j, spec in enumerate(plan.period_specs):
        name = f"layer{j}"
        if spec.kind != "attn":
            continue
        c = caches["blocks"][name]
        # layer indices owned by this period slot, across blocks
        idxs = [li + n * attn_per_period for n in range(plan.n_blocks)]
        karr = np.stack([kv.k[i2].transpose(1, 0, 2) for i2 in idxs])
        varr = np.stack([kv.v[i2].transpose(1, 0, 2) for i2 in idxs])
        k_buf = c["k"].at[:, batch_idx, :upto].set(_put_like(c["k"], karr))
        v_buf = c["v"].at[:, batch_idx, :upto].set(_put_like(c["v"], varr))
        new_blocks[name] = {"k": k_buf, "v": v_buf}
        li += 1
    return {"prefix": new_prefix, "blocks": new_blocks}


# ---------------------------------------------------------------------------
# Paged decode arena (DESIGN.md §12)
# ---------------------------------------------------------------------------
def init_paged_pools(cfg, num_pages: int, page_size: int, group: int):
    """Build the paged arena's device pools: ``(pool, qcodes, qscales)``.

    ``pool`` mirrors ``init_cache``'s pytree with the (batch, max_len)
    leading axes replaced by (num_pages, page_size) — logical position
    ``t`` of a slot lives at row ``t % page_size`` of the pool page named
    by entry ``t // page_size`` of its block table.  ``qcodes``/
    ``qscales`` are the parallel quantized pools (int8 codes + f32
    scales, one scale per ``group`` channels per token) sharing the SAME
    page ids: a page holds either fp content or quantized content, and
    the per-slot ``quant_len`` decides which pool each position reads
    from.  Page 0 is the reserved scratch page (never allocated)."""
    from repro.models import init_cache

    pool = init_cache(cfg, num_pages, max_len=page_size)
    qcodes = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.int8), pool)
    qscales = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape[:-1] + (a.shape[-1] // group,),
                            jnp.float32), pool)
    return pool, qcodes, qscales


def _paged_view(leaf, bt, prefix: bool):
    """Gather a pool leaf into the dense (·, B, S, H, D) decode view."""
    if prefix:  # (P, ps, H, D) -> (B, PPS*ps, H, D)
        g = jnp.take(leaf, bt, axis=0)
        return g.reshape(g.shape[0], -1, *g.shape[3:])
    g = jnp.take(leaf, bt, axis=1)  # (n, B, PPS, ps, H, D)
    return g.reshape(g.shape[0], g.shape[1], -1, *g.shape[4:])


def _blend_quant(view, qc_view, qs_view, quant_len, prefix: bool):
    """Dequantize the quant-pool view and take it for positions below
    each slot's ``quant_len`` (exactly the ``group_dequantize`` math:
    signed codes x f32 scale, then cast to the cache compute dtype)."""
    d = view.shape[-1]
    g = d // qs_view.shape[-1]
    x = qc_view.astype(jnp.float32).reshape(qc_view.shape[:-1] + (d // g, g))
    x = (x * qs_view[..., None].astype(jnp.float32)
         ).reshape(qc_view.shape).astype(view.dtype)
    s = view.shape[1] if prefix else view.shape[2]
    use_q = jnp.arange(s, dtype=jnp.int32)[None, :] < quant_len[:, None]
    m = use_q[:, :, None, None] if prefix else use_q[None, :, :, None, None]
    return jnp.where(m, x, view)


def _pad_axis(x, target: int, axis: int):
    cur = x.shape[axis]
    if cur >= target:
        return jax.lax.slice_in_dim(x, 0, target, axis=axis)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - cur)
    return jnp.pad(x, pad)


@lru_cache(maxsize=8)
def _paged_steps(cfg_name: str, page_size: int):
    """Jitted paged-arena kernels for one model config: ``(arena, copy)``.

    ``arena(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
    mask)`` is the paged analogue of ``_jitted_steps``'s arena decode:
    gather every slot's pages into a contiguous view (dequant-blending
    quantized-resident positions), run one masked ``decode_step``, then
    scatter ONLY the newly written K/V row back to each slot's page.
    Parked rows (mask False) are pinned to the view's last position,
    which maps to the scratch page or the slot's own never-attended tail
    row, so their writes are inert — same contract as the dense arena.
    Block tables and lengths are traced: page churn never recompiles.

    ``copy(pool, src, bt_row, src_idx)`` is ``copy_cache_slot`` as a
    page-map operation: one prefilled source row lands in the slot's
    owned pages (sentinel-0 tail entries spill into scratch).
    """
    from repro.models import decode_step

    cfg = get_config(cfg_name)

    def arena(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
              mask):
        view_len = bt.shape[1] * page_size
        pos = jnp.where(mask, pos, view_len - 1).astype(jnp.int32)

        def build(prefix):
            def f(p, qc, qs):
                return _blend_quant(_paged_view(p, bt, prefix),
                                    _paged_view(qc, bt, prefix),
                                    _paged_view(qs, bt, prefix),
                                    quant_len, prefix)
            return f

        caches = {
            "prefix": jax.tree_util.tree_map(
                build(True), pool["prefix"], qcodes["prefix"],
                qscales["prefix"]),
            "blocks": jax.tree_util.tree_map(
                build(False), pool["blocks"], qcodes["blocks"],
                qscales["blocks"]),
        }
        logits, new_caches = decode_step(cfg, params, caches, tokens, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

        page_idx = jnp.take_along_axis(
            bt, (pos // page_size)[:, None], axis=1)[:, 0]
        offset = pos % page_size

        def scat(prefix):
            def f(p, nv):
                if prefix:
                    row = jnp.take_along_axis(
                        nv, pos[:, None, None, None], axis=1)[:, 0]
                    return p.at[page_idx, offset].set(row.astype(p.dtype))
                row = jnp.take_along_axis(
                    nv, pos[None, :, None, None, None], axis=2)[:, :, 0]
                return p.at[:, page_idx, offset].set(row.astype(p.dtype))
            return f

        new_pool = {
            "prefix": jax.tree_util.tree_map(
                scat(True), pool["prefix"], new_caches["prefix"]),
            "blocks": jax.tree_util.tree_map(
                scat(False), pool["blocks"], new_caches["blocks"]),
        }
        return jnp.where(mask, nxt, 0), new_pool

    def copy(pool, src, bt_row, src_idx):
        pps = bt_row.shape[0]

        def w_prefix(p, s):
            row = jax.lax.dynamic_slice_in_dim(s, src_idx, 1, 0)[0]
            row = _pad_axis(row, pps * page_size, axis=0)
            pages = row.reshape(pps, page_size, *row.shape[1:])
            return p.at[bt_row].set(pages.astype(p.dtype))

        def w_block(p, s):
            row = jax.lax.dynamic_slice_in_dim(s, src_idx, 1, 1)[:, 0]
            row = _pad_axis(row, pps * page_size, axis=1)
            pages = row.reshape(row.shape[0], pps, page_size,
                                *row.shape[2:])
            return p.at[:, bt_row].set(pages.astype(p.dtype))

        return {
            "prefix": jax.tree_util.tree_map(w_prefix, pool["prefix"],
                                             src["prefix"]),
            "blocks": jax.tree_util.tree_map(w_block, pool["blocks"],
                                             src["blocks"]),
        }

    return jax.jit(arena), jax.jit(copy)


@lru_cache(maxsize=16)
def _paged_verify_steps(cfg_name: str, page_size: int, width: int):
    """Jitted paged multi-token verify step (DESIGN.md §15).

    ``verify(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
    mask)`` is ``_paged_steps``'s arena decode widened to a ``(B, width)``
    query block: each live slot feeds ``width`` tokens at consecutive
    positions ``pos..pos+width-1`` and gets all ``width`` greedy argmax
    outputs back for host-side accept-prefix matching.  All ``width`` K/V
    rows are scattered to each slot's pages; positions beyond a slot's
    *ensured* page span map to block-table entry 0 — the reserved scratch
    page, which no live query ever reads — so slots verifying fewer than
    ``width-1`` drafts need no masking: their surplus writes are inert by
    construction.  Parked rows pin to ``view_len - width`` (scratch pages
    again).  Rejected suffixes are rolled back by the caller via
    ``PageTable.release_tail``; the pages themselves need no scrubbing
    because reads are capped at each slot's committed ``pos``.
    """
    from repro.models import decode_step

    cfg = get_config(cfg_name)

    def verify(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
               mask):
        view_len = bt.shape[1] * page_size
        pos = jnp.where(mask, pos, view_len - width).astype(jnp.int32)

        def build(prefix):
            def f(p, qc, qs):
                return _blend_quant(_paged_view(p, bt, prefix),
                                    _paged_view(qc, bt, prefix),
                                    _paged_view(qs, bt, prefix),
                                    quant_len, prefix)
            return f

        caches = {
            "prefix": jax.tree_util.tree_map(
                build(True), pool["prefix"], qcodes["prefix"],
                qscales["prefix"]),
            "blocks": jax.tree_util.tree_map(
                build(False), pool["blocks"], qcodes["blocks"],
                qscales["blocks"]),
        }
        logits, new_caches = decode_step(cfg, params, caches, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, width)

        new_pool = pool
        for j in range(width):
            pj = pos + j
            page_idx = jnp.take_along_axis(
                bt, (pj // page_size)[:, None], axis=1)[:, 0]
            offset = pj % page_size

            def scat(prefix, pj=pj, page_idx=page_idx, offset=offset):
                def f(p, nv):
                    if prefix:
                        row = jnp.take_along_axis(
                            nv, pj[:, None, None, None], axis=1)[:, 0]
                        return p.at[page_idx, offset].set(row.astype(p.dtype))
                    row = jnp.take_along_axis(
                        nv, pj[None, :, None, None, None], axis=2)[:, :, 0]
                    return p.at[:, page_idx, offset].set(row.astype(p.dtype))
                return f

            new_pool = {
                "prefix": jax.tree_util.tree_map(
                    scat(True), new_pool["prefix"], new_caches["prefix"]),
                "blocks": jax.tree_util.tree_map(
                    scat(False), new_pool["blocks"], new_caches["blocks"]),
            }
        return jnp.where(mask[:, None], nxt, 0), new_pool

    return jax.jit(verify)


def copy_cache_slot_paged(cfg, pool, src, bt_row, page_size: int,
                          src_idx: int = 0):
    """Paged ``copy_cache_slot``: land one prefilled source row in the
    pages of ``bt_row`` (a (PPS,) int32 row; 0 entries spill to scratch)."""
    if "self" in pool:
        raise NotImplementedError("paged arena: decoder-only caches")
    _, copy = _paged_steps(cfg.name, page_size)
    return copy(pool, src, jnp.asarray(bt_row, jnp.int32),
                jnp.asarray(src_idx, jnp.int32))


def _paged_scatter(cfg, pool, bt_row, k_arr, v_arr, upto: int,
                   page_size: int):
    """Scatter per-layer (L, H, S, X) k/v arrays into a slot's pages —
    the page-map core of ``inject_kv_paged``/``inject_quant_pages``.
    Only the first ``ceil(upto / page_size)`` owned pages are written
    (partial-page tails are zero-filled; the slot is fresh, so nothing
    real is clobbered).  The arrays move to the pool's device first."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    n_used = -(-upto // page_size)
    rows = jnp.asarray(np.asarray(bt_row)[:n_used], jnp.int32)
    where = jax.tree_util.tree_leaves(pool)[0].sharding
    li = 0

    def _pages(arr):  # (H, S, X) -> (n_used, ps, H, X)
        a = jax.device_put(arr, where).swapaxes(0, 1)  # (S, H, X)
        a = _pad_axis(a, n_used * page_size, axis=0)
        return a.reshape(n_used, page_size, *a.shape[1:])

    new_prefix = {}
    for i, spec in enumerate(plan.prefix_specs):
        name = f"layer{i}"
        c = pool["prefix"][name]
        if spec.kind != "attn":
            new_prefix[name] = c
            continue
        new_prefix[name] = {
            "k": c["k"].at[rows].set(_pages(k_arr[li]).astype(c["k"].dtype)),
            "v": c["v"].at[rows].set(_pages(v_arr[li]).astype(c["v"].dtype)),
        }
        li += 1
    new_blocks = dict(pool["blocks"])
    attn_per_period = len([s for s in plan.period_specs if s.kind == "attn"])
    for j, spec in enumerate(plan.period_specs):
        name = f"layer{j}"
        if spec.kind != "attn":
            continue
        c = pool["blocks"][name]
        idxs = [li + n * attn_per_period for n in range(plan.n_blocks)]
        karr = jnp.stack([_pages(k_arr[i2]) for i2 in idxs])
        varr = jnp.stack([_pages(v_arr[i2]) for i2 in idxs])
        new_blocks[name] = {
            "k": c["k"].at[:, rows].set(karr.astype(c["k"].dtype)),
            "v": c["v"].at[:, rows].set(varr.astype(c["v"].dtype)),
        }
        li += 1
    return {"prefix": new_prefix, "blocks": new_blocks}


def inject_kv_paged(cfg, pool, bt_row, kv: KVCache, page_size: int):
    """Paged ``inject_kv``: write a restored KVCache into a fresh slot's
    pages as a page-map operation."""
    return _paged_scatter(cfg, pool, bt_row, kv.k, kv.v, kv.seq, page_size)


def inject_quant_pages(cfg, qcodes, qscales, bt_row, k_codes, k_scales,
                       v_codes, v_scales, upto: int, page_size: int):
    """Land packed quantized KV straight in the quant page pools — the
    zero-materialization injection path for paged-eligible strategies.
    ``k_codes``/``v_codes`` are (L, H, S, D) signed int8;
    ``k_scales``/``v_scales`` are (L, H, S, D/group) f32 (already
    round-tripped through fp16, so the fused dequant is bit-identical
    to the materialized ``group_dequantize`` + inject path)."""
    new_qc = _paged_scatter(cfg, qcodes, bt_row, k_codes, v_codes, upto,
                            page_size)
    new_qs = _paged_scatter(cfg, qscales, bt_row, k_scales, v_scales, upto,
                            page_size)
    return new_qc, new_qs


# ---------------------------------------------------------------------------
# Quality evaluation
# ---------------------------------------------------------------------------
@lru_cache(maxsize=8)
def _jitted_steps(cfg_name: str, seq: int, batch: int, max_len: int):
    """Returns (prefill, decode, arena_decode), all jitted.

    ``arena_decode(params, caches, tokens, pos, mask)`` is the masked
    batched decode of the slot arena (DESIGN.md §9): ``tokens`` (B, 1),
    ``pos`` (B,) per-slot next cache positions, ``mask`` (B,) live-slot
    flags.  Every slot advances in ONE model call; parked rows (mask
    False — free slots and this iteration's fresh prefills) are pinned to
    the scratch position ``max_len - 1``, which no live query position
    ever attends to, so their cache writes are inert.  The next token per
    slot comes from an on-device argmax; the caller pulls the (B,) token
    vector back once per iteration.
    """
    from repro.models import decode_step, prefill

    cfg = get_config(cfg_name)
    pre = jax.jit(lambda p, b: prefill(cfg, p, b, max_len=max_len))
    dec = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))

    def _arena(p, c, t, pos, mask):
        pos = jnp.where(mask, pos, max_len - 1).astype(jnp.int32)
        logits, c = decode_step(cfg, p, c, t, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return jnp.where(mask, nxt, 0), c

    return pre, dec, jax.jit(_arena)


@lru_cache(maxsize=16)
def _verify_steps(cfg_name: str, max_len: int, width: int):
    """Jitted dense multi-token verify step (DESIGN.md §15).

    ``verify(params, caches, tokens, pos, mask)`` widens ``_jitted_steps``'s
    arena decode to a ``(B, width)`` query block: each live slot feeds its
    last committed token plus ``width-1`` draft tokens at consecutive
    positions ``pos..pos+width-1`` and receives all ``width`` greedy argmax
    outputs for host-side accept-prefix matching.  Parked rows pin to
    ``max_len - width`` so every one of their ``width`` K/V row writes
    stays in-bounds; the writes are inert because rows are per-slot and
    reads are capped at each slot's committed position (``kv_valid``), so
    garbage beyond ``pos`` — including rejected draft KV — is simply
    overwritten by later steps and never attended to.
    """
    from repro.models import decode_step

    cfg = get_config(cfg_name)

    def _verify(p, c, t, pos, mask):
        pos = jnp.where(mask, pos, max_len - width).astype(jnp.int32)
        logits, c = decode_step(cfg, p, c, t, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, width)
        return jnp.where(mask[:, None], nxt, 0), c

    return jax.jit(_verify)


def _prompts_for(workload: str, n: int, seq: int, seed: int
                 ) -> Tuple[jnp.ndarray, List[str]]:
    tok = ByteTokenizer()
    rng = np.random.default_rng(seed)
    rows, answers = [], []
    for _ in range(n):
        prompt, ans = make_prompt(workload, rng, approx_len=seq + 32)
        ids = tok.encode(prompt)
        ids = ids[-seq:] if len(ids) >= seq else tok.pad_to(ids, seq)
        rows.append(ids)
        answers.append(ans)
    return jnp.asarray(np.stack(rows)), answers


def _greedy_decode(dec_fn, params, caches, first_tokens, start_pos: int,
                   steps: int) -> np.ndarray:
    toks = first_tokens  # (B, 1)
    out = [np.asarray(toks)[:, 0]]
    pos = jnp.asarray(start_pos, jnp.int32)
    for t in range(steps):
        logits, caches = dec_fn(params, caches, toks, pos + t)
        toks = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
        # lint: sync-ok(offline reference decode for agreement scoring)
        out.append(np.asarray(toks)[:, 0])
    return np.stack(out, axis=1)  # (B, steps+1)


def _teacher_forced_agreement(dec_fn, params, caches, ref_tokens: np.ndarray,
                              start_pos: int) -> float:
    """Relative accuracy without divergence compounding: feed the reference
    continuation, compare each step's argmax against the reference's next
    token (the paper's relative-accuracy analogue)."""
    b, t1 = ref_tokens.shape
    pos = jnp.asarray(start_pos, jnp.int32)
    hits, total = 0, 0
    for t in range(t1 - 1):
        toks = jnp.asarray(ref_tokens[:, t:t + 1], jnp.int32)
        logits, caches = dec_fn(params, caches, toks, pos + t)
        pred = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        hits += int((pred == ref_tokens[:, t + 1]).sum())
        total += b
    return hits / max(total, 1)


def evaluate_quality(
    strategy: StrategyConfig,
    workloads: Sequence[str] = tuple(WORKLOADS),
    n_prompts: int = 6,
    seq: int = 192,
    decode_tokens: int = 20,
    seed: int = 0,
    ref=None,
    head_scores: Optional[np.ndarray] = None,
) -> Dict[str, float]:
    """Per-workload relative accuracy of ``strategy`` on the tiny LM."""
    if is_identity(strategy):
        return {w: 1.0 for w in workloads}
    cfg, params = ref if ref is not None else get_reference_model()
    gen_budget = decode_tokens + 2
    pre, dec, _ = _jitted_steps(cfg.name, seq, n_prompts, seq + gen_budget)
    pipe = CompressionPipeline(strategy, head_scores=head_scores)

    out: Dict[str, float] = {}
    for wi, w in enumerate(workloads):
        tokens, _ = _prompts_for(w, n_prompts, seq, seed * 7919 + wi)
        logits, caches = pre(params, {"tokens": tokens})
        first = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)

        # reference decode (uncompressed KV)
        ref_toks = _greedy_decode(dec, params, caches, first, seq,
                                  decode_tokens)

        # compressed-KV decode, teacher-forced on the reference tokens
        comp_caches = caches
        for b in range(n_prompts):
            kv = extract_kv(cfg, caches, b, upto=seq)
            restored = pipe.decompress(pipe.compress(kv))
            comp_caches = inject_kv(cfg, comp_caches, b, restored)
        out[w] = _teacher_forced_agreement(dec, params, comp_caches,
                                           ref_toks, seq)
    return out


def calibrate_head_scores(workload: str = "mixed", n_prompts: int = 4,
                          seq: int = 192, seed: int = 0, ref=None
                          ) -> np.ndarray:
    """Data-driven retrieval-head scores (L, H) from real model KV."""
    cfg, params = ref if ref is not None else get_reference_model()
    pre, _, _ = _jitted_steps(cfg.name, seq, n_prompts, seq + 4)
    ws = list(WORKLOADS) if workload == "mixed" else [workload]
    scores = []
    for wi, w in enumerate(ws):
        tokens, _ = _prompts_for(w, n_prompts, seq, seed + wi)
        _, caches = pre(params, {"tokens": tokens})
        for b in range(min(n_prompts, 2)):
            kv = extract_kv(cfg, caches, b, upto=seq)
            centered = kv.k - kv.k.mean(axis=2, keepdims=True)
            scores.append(np.sqrt((centered**2).mean(axis=(2, 3))))
    return np.mean(scores, axis=0)
