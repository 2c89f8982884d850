"""The decode arena's two layouts, one object each (DESIGN.md §9, §12).

A :class:`~repro.serving.workers.DecodeWorker` holds one layout and asks
it for everything that depends on where a slot's rows sit in device
memory:

* :class:`DenseArena` — ONE cache pytree with a leading slot axis, every
  slot ``max_len`` rows long;
* :class:`PagedArena` — ``(num_pages, page_size)`` pools with per-slot
  block tables over a shared free list
  (:class:`~repro.core.kvcache.PageTable`), and parallel quantized pools
  that paged-eligible payloads land in as packed pages, dequantized
  inside the step.

Both have the same methods: :meth:`allocate` the device state, land a
prefilled row (``land_prefill``), ``inject`` a restored KV and its
state, land packed quantized pages (``land_pages``; only a layout with
:attr:`fused_dequant` can), ``grow`` a slot before a step, the step
:meth:`program` with the layout's ``operands``, and ``release`` a slot
or its tail.  Each step program takes a token block ``(B, w)`` and
returns ``(B, w)`` greedy tokens: plain decode is ``w = 1``, a
speculative verify ``w = 1 + drafts`` (DESIGN.md §15).
"""
from __future__ import annotations

from functools import lru_cache
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import codecs
from repro.core.kvcache import PageTable
from repro.core.pipeline import CompressedKV
from repro.core.quality import (
    _jitted_steps,
    _layers_of,
    _with_layers,
    copy_cache_slot,
    inject_kv,
)
from repro.core.strategy import paged_eligible


class _Arena:
    """What both layouts share: the model (read through its handle), the
    slot count and row length, the device, the cache pytree (None until
    ``allocate``, which places it on ``device`` directly: never staged
    through the default device, which may be another worker's) and the
    step program, built once."""

    # Whether the step dequantizes quantized pages inside attention, so a
    # paged-eligible payload lands without a materialized decompress.
    fused_dequant = False
    # Whether a parked slot's SSM state is held across the step.
    holds_state = True
    page_table: Optional[PageTable] = None

    def __init__(self, model: Any, n_slots: int, max_len: int,
                 device: Optional[jax.Device]):
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.device = device
        self.cache: Any = None
        self._program = None

    def fuses(self, strategy, head_dim: Optional[int]) -> bool:
        """Whether a payload of ``strategy`` lands as quantized pages."""
        return self.fused_dequant and paged_eligible(strategy,
                                                     head_dim=head_dim)

    def program(self):
        """The jitted step: ``(params, cache, *operands(), tokens (B, w),
        positions (B,), mask (B,)) -> (tokens (B, w), cache)``."""
        if self._program is None:
            self._program = self._build_program()
        return self._program


class DenseArena(_Arena):
    """ONE cache pytree, leading axis ``n_slots``, ``max_len`` rows per
    slot; the step is ``_jitted_steps``'s ``_arena``."""

    def __init__(self, model: Any, n_slots: int, max_len: int, seq: int,
                 device: Optional[jax.Device] = None):
        super().__init__(model, n_slots, max_len, device)
        self.seq = seq

    def allocate(self) -> None:
        from repro.models.transformer import init_cache
        with jax.default_device(self.device):
            self.cache = init_cache(self.model.cfg, self.n_slots,
                                    self.max_len)

    def _build_program(self):
        return _jitted_steps(self.model.cfg.name, self.seq, self.n_slots,
                             self.max_len)[2]

    @property
    def arrays(self):
        return (self.cache,)

    def operands(self):
        return ()

    def land_prefill(self, caches, idx: int, upto: int) -> None:
        del upto
        self.cache = copy_cache_slot(self.model.cfg, self.cache, caches, idx)

    def inject(self, kv, idx: int) -> None:
        self.cache = inject_kv(self.model.cfg, self.cache, idx, kv)

    def land_pages(self, comp: CompressedKV, idx: int) -> int:
        raise NotImplementedError("the dense arena holds no quantized pages")

    def grow(self, idx: int, upto: int) -> None:
        """Every slot holds ``max_len`` rows from the start."""

    def release(self, idx: int, upto: int = 0) -> None:
        """Rows are reused as they are: reads stop at a slot's position."""


class PagedArena(_Arena):
    """``(num_pages, page_size)`` pools allocated page by page on demand
    (DESIGN.md §12).  A slot's positions below its ``quant_len`` read the
    quantized pools; the rest read the fp pool."""

    fused_dequant = True
    holds_state = False

    def __init__(self, model: Any, n_slots: int, max_len: int,
                 page_size: int, num_pages: Optional[int] = None,
                 device: Optional[jax.Device] = None):
        super().__init__(model, n_slots, max_len, device)
        self.page_size = page_size
        self.pps = -(-max_len // page_size)     # block-table row length
        self.page_table = PageTable(num_pages or n_slots * self.pps + 1,
                                    page_size)
        self.qcodes: Any = None
        self.qscales: Any = None
        self.quant_len = np.zeros(n_slots, np.int32)

    def allocate(self) -> None:
        # Per-channel scale layout (group=1): any strategy's group maps
        # onto it by broadcasting its group scale, so one pool serves
        # every eligible profile.
        with jax.default_device(self.device):
            self.cache, self.qcodes, self.qscales = init_paged_pools(
                self.model.cfg, self.page_table.num_pages, self.page_size,
                group=1)

    def _build_program(self):
        return _paged_steps(self.model.cfg.name, self.page_size)[0]

    @property
    def arrays(self):
        return (self.cache, self.qcodes, self.qscales)

    def operands(self):
        bt = np.zeros((self.n_slots, self.pps), np.int32)
        for s, owned in self.page_table.pages.items():
            bt[s, :len(owned)] = owned
        return (self.qcodes, self.qscales, jnp.asarray(bt),
                jnp.asarray(self.quant_len))

    def _row(self, idx: int, upto: int) -> np.ndarray:
        """Slot ``idx`` grown to ``upto`` positions: its block-table row."""
        self.page_table.ensure(idx, upto)
        return self.page_table.block_row(idx, self.pps)

    def land_prefill(self, caches, idx: int, upto: int) -> None:
        if "self" in self.cache:
            raise NotImplementedError("paged arena: decoder-only caches")
        _, copy = _paged_steps(self.model.cfg.name, self.page_size)
        self.cache = copy(self.cache, caches,
                          jnp.asarray(self._row(idx, upto), jnp.int32),
                          jnp.asarray(0, jnp.int32))
        self.quant_len[idx] = 0

    def inject(self, kv, idx: int) -> None:
        self.cache = _paged_scatter(self.model.cfg, self.cache,
                                    self._row(idx, kv.seq), kv.k, kv.v,
                                    kv.seq, self.page_size)
        self.quant_len[idx] = 0

    def land_pages(self, comp: CompressedKV, idx: int) -> int:
        """Land a paged-eligible payload's packed codes and scales straight
        in the quant pools: no materialized decompress, the step
        dequantizes them.  Returns the bytes landed."""
        (kc, ks), (vc, vs) = quant_entry_arrays(comp)
        seq = comp.shape[2]
        row = self._row(idx, seq)
        cfg = self.model.cfg
        self.qcodes = _paged_scatter(cfg, self.qcodes, row, kc, vc, seq,
                                     self.page_size)
        self.qscales = _paged_scatter(cfg, self.qscales, row, ks, vs, seq,
                                      self.page_size)
        self.quant_len[idx] = seq
        return kc.nbytes + ks.nbytes + vc.nbytes + vs.nbytes

    def grow(self, idx: int, upto: int) -> None:
        self.page_table.ensure(idx, upto)

    def release(self, idx: int, upto: int = 0) -> None:
        """All of slot ``idx``'s pages, or (``upto`` > 0) those past its
        first ``upto`` positions: a speculative step's rejected tail."""
        if upto:
            self.page_table.release_tail(idx, upto)
        else:
            self.page_table.release(idx)
            self.quant_len[idx] = 0


# ---------------------------------------------------------------------------
# Paged device programs
# ---------------------------------------------------------------------------
def quant_entry_arrays(comp: CompressedKV):
    """Unpack a paged-eligible :class:`CompressedKV` into page-pool form:
    ``((k_codes, k_scales), (v_codes, v_scales))`` with codes (L, H, S, D)
    signed int8 and scales (L, H, S, D) per-channel f32 (the stored fp16
    group scale broadcast across its group — numerically identical to the
    grouped multiply, so the fused dequant is bit-for-bit equal to
    ``group_dequantize`` + materialized injection).

    Only valid when ``paged_eligible(comp.strategy)``: one symmetric
    per-token bucket per tensor, codec "none", no transform."""
    L, H, S, D = comp.shape
    out = []
    for wires in (comp.k_buckets, comp.v_buckets):
        assert len(wires) == 1, "paged-eligible strategies are single-bucket"
        w = wires[0]
        count = int(np.prod(w.codes_shape))
        codes = codecs.decode_codes(w.payload, w.bits, count,
                                    comp.strategy.codec)
        codes = codes.reshape(w.codes_shape)          # (N, S, D) uint8
        signed = (codes.astype(np.int16)
                  - (1 << (w.bits - 1))).astype(np.int8)
        sc = w.scale.astype(np.float32)[..., 0]       # (N, S, D/group)
        sc = np.repeat(sc, w.group_size, axis=2)[:, :, :D]
        arr = np.zeros((L, H, S, D), np.int8)
        sarr = np.zeros((L, H, S, D), np.float32)
        ls, hs = w.lh_index[:, 0], w.lh_index[:, 1]
        arr[ls, hs] = signed
        sarr[ls, hs] = sc
        out.append((arr, sarr))
    return out[0], out[1]


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
    """Jitted paged-arena programs for one model config: ``(arena, copy)``.

    ``arena(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
    mask)`` is the paged analogue of ``_jitted_steps``'s arena step at
    width ``w`` (``tokens`` (B, w)): gather every slot's pages into a
    contiguous view (dequant-blending quantized-resident positions), run
    one masked ``decode_step``, then scatter ONLY the ``w`` newly written
    K/V rows back to each slot's pages.  Rows past a slot's ensured page
    span map to block-table entry 0, the scratch page no live query
    reads, so a slot verifying fewer than ``w - 1`` drafts needs no
    masking.  Parked rows (mask False) are pinned to the view's last
    ``w`` positions, which map to the scratch page or the slot's own
    never-attended tail, so their writes are inert — the dense arena's
    contract.  Block tables and lengths are traced: page churn never
    recompiles.

    ``copy(pool, src, bt_row, src_idx)`` is ``copy_cache_slot`` as a
    page-map operation: one prefilled source row lands in the slot's
    owned pages (sentinel-0 tail entries spill into scratch).
    """
    from repro.models import decode_step

    cfg = get_config(cfg_name)
    parts = ("prefix", "blocks")

    def arena(params, pool, qcodes, qscales, bt, quant_len, tokens, pos,
              mask):
        width = tokens.shape[1]
        view_len = bt.shape[1] * page_size
        pos = jnp.where(mask, pos, view_len - width).astype(jnp.int32)

        def view(prefix):
            def f(p, qc, qs):
                return _blend_quant(_paged_view(p, bt, prefix),
                                    _paged_view(qc, bt, prefix),
                                    _paged_view(qs, bt, prefix),
                                    quant_len, prefix)
            return f

        caches = {part: jax.tree_util.tree_map(
            view(part == "prefix"), pool[part], qcodes[part], qscales[part])
            for part in parts}
        logits, new_caches = decode_step(cfg, params, caches, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, width)

        def write(prefix, at):
            page = jnp.take_along_axis(bt, (at // page_size)[:, None],
                                       axis=1)[:, 0]
            offset = at % page_size

            def f(p, nv):
                if prefix:
                    row = jnp.take_along_axis(
                        nv, at[:, None, None, None], axis=1)[:, 0]
                    return p.at[page, offset].set(row.astype(p.dtype))
                row = jnp.take_along_axis(
                    nv, at[None, :, None, None, None], axis=2)[:, :, 0]
                return p.at[:, page, offset].set(row.astype(p.dtype))
            return f

        for j in range(width):
            pool = {part: jax.tree_util.tree_map(
                write(part == "prefix", pos + j), pool[part],
                new_caches[part]) for part in parts}
        return jnp.where(mask[:, None], nxt, 0), pool

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


def _paged_scatter(cfg, pool, bt_row, k_arr, v_arr, upto: int,
                   page_size: int):
    """Scatter (L, H, S, X) k/v arrays, layers in hand-off order, into a
    slot's pages: a restored KV into the fp pool, or packed codes and
    scales into the quant pools.  Only the first ``ceil(upto /
    page_size)`` owned pages are written (partial-page tails are
    zero-filled; the slot is fresh, so nothing real is clobbered).  Each
    array moves to the pool's device as it is held and is cast there."""
    n_used = -(-upto // page_size)
    rows = jnp.asarray(np.asarray(bt_row)[:n_used], jnp.int32)
    prefix, blocks, _ = _layers_of(cfg, pool, "attn")
    where = jax.tree_util.tree_leaves(pool)[0].sharding

    def pages(arr):  # (L, H, S, X) -> (L, n_used, ps, H, X)
        a = jax.device_put(arr, where).swapaxes(1, 2)
        a = _pad_axis(a, n_used * page_size, axis=1)
        return a.reshape(a.shape[0], n_used, page_size, *a.shape[2:])

    kv = {"k": pages(k_arr), "v": pages(v_arr)}
    n_prefix, n = len(prefix), len(blocks)
    new_prefix = tuple({t: c[t].at[rows].set(kv[t][i].astype(c[t].dtype))
                        for t in kv} for i, c in enumerate(prefix))
    new_blocks = tuple({t: c[t].at[:, rows].set(
        kv[t][n_prefix + j::n].astype(c[t].dtype)) for t in kv}
        for j, c in enumerate(blocks))
    return _with_layers(cfg, pool, "attn", new_prefix, new_blocks)
