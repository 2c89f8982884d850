"""Worker abstractions of the disaggregated serving runtime.

The paper's deployment target is a PD-separated *cluster*: many prefill
workers feeding many decode workers over heterogeneous links.  This module
holds the two worker types that
:class:`~repro.serving.cluster.ClusterRuntime` composes N x M (and that the
1x1 :class:`~repro.serving.engine.ServingRuntime` facade is built from):

* :class:`PrefillWorker` — one prefill engine: its own jitted batch-1
  prefill stream, the codec-cost model for the compress stage it feeds the
  egress link, and the controller/static profile selection for the KV it
  emits.  Within an iteration, requests assigned to the same prefill
  worker serialize on it (the ``busy`` offset); requests on different
  workers run concurrently.
* :class:`DecodeWorker` — one decode engine: its own fixed-capacity slot
  arena (ONE cache pytree with a leading slot axis, advanced by a single
  masked jitted decode per iteration), its own local slot-id pool, and its
  own decode-side KV tier hierarchy (HBM/DRAM are worker-local; the remote
  pool tier may be shared cluster-wide — see
  :class:`~repro.serving.kvstore.TieredKVStore`).

Both workers read the model through a shared mutable :class:`ModelHandle`
so a runtime-level swap of (cfg, params) — the test fixtures pin the
session-cached reference model this way — reaches every worker.

This module also owns the pieces the old monolithic engine shared between
its one-shot and continuous paths: :class:`RuntimeConfig`,
:class:`ServedRequest`, the PD codec stages (:func:`compress_kvs` /
:func:`decompress_kvs`) and the demotion re-compression hook
(:func:`recompress_entry`).  ``repro.serving.engine`` re-exports them, so
existing imports keep working.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.controller import Decision, ServiceAwareController, ServiceContext
from repro.core import codecs
from repro.core.kvcache import PageTable
from repro.core.pipeline import CompressedKV, CompressionPipeline
from repro.core.profiles import Profile
from repro.core.quality import (
    _jitted_steps,
    _paged_steps,
    copy_cache_slot,
    copy_cache_slot_paged,
    extract_kv,
    init_paged_pools,
    inject_kv,
    inject_kv_paged,
    inject_quant_pages,
)
from repro.core.strategy import StrategyConfig, paged_eligible
from repro.serving import tracing
from repro.serving.kvstore import TierSpec
from repro.serving.request import Request


def _select_profile(controller: Optional[ServiceAwareController],
                    static_profile: Optional[Profile],
                    ctx: ServiceContext
                    ) -> Tuple[Profile, Optional[Decision]]:
    """Shared controller / static / identity three-way profile choice."""
    if controller is not None:
        d = controller.select(ctx)
        return d.profile, d
    if static_profile is not None:
        return static_profile, None
    from repro.core.profiles import IDENTITY_PROFILE
    return IDENTITY_PROFILE, None


# ---------------------------------------------------------------------------
# Shared PD codec stages (one-shot engine AND per-request continuous runtime)
# ---------------------------------------------------------------------------
def compress_kvs(strategy: StrategyConfig, kvs: Sequence[Any]
                 ) -> Tuple[List[Any], int, float]:
    """Compress each KV prefix for the wire.  Returns
    ``(payloads, wire_bytes, measured_seconds)``."""
    pipe = CompressionPipeline(strategy)
    with tracing.timed("compress") as sp:
        comps = [pipe.compress(kv) for kv in kvs]
        if sp:
            sp.add(kv_bytes=sum(kv.nbytes_wire() for kv in kvs),
                   wire_bytes=sum(c.total_bytes() for c in comps))
    return comps, sum(c.total_bytes() for c in comps), sp.seconds


def decompress_kvs(comps: Sequence[CompressedKV]
                   ) -> Tuple[List[Any], float]:
    """Restore wire payloads to KV.  Returns ``(kvs, measured_seconds)``."""
    with tracing.timed("restore") as sp:
        kvs = [CompressionPipeline(c.strategy).decompress(c) for c in comps]
        if sp:
            sp.add(bytes=sum(kv.k.nbytes + kv.v.nbytes for kv in kvs))
    return kvs, sp.seconds


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


def recompress_entry(entry, profile: Profile) -> Optional[Tuple[Any, int]]:
    """Tier demotion / refetch-smaller hook: really re-encode a stored
    ``(CompressedKV, first, s_dec)`` payload with ``profile``.  Returns
    None when it would not shrink."""
    comp, first, _ = entry.payload
    if comp.strategy == profile.strategy:
        return None
    restored, _ = decompress_kvs([comp])
    comps, wire, _ = compress_kvs(profile.strategy, restored)
    if wire >= entry.wire_bytes:
        return None
    return (comps[0], first, profile.s_dec), wire


# ---------------------------------------------------------------------------
# Runtime configuration / outcomes
# ---------------------------------------------------------------------------
@dataclass
class RuntimeConfig:
    seq: int = 96                 # prompt tokens (padded/truncated)
    decode_tokens: int = 12       # generation budget per request
    # Serving scenario: "pool" = KV-disaggregated prefix caching (cold
    # requests prefill locally, pool writes are off the critical path);
    # "pd" = PD separation (every cold request's compressed KV crosses the
    # serialized wire prefill -> compress -> transfer -> decompress ->
    # decode, ON the critical path).
    mode: str = "pool"
    # Virtual-clock cost model.  None = measure wall-clock (real execution
    # time of the tiny model); a float models a loaded cluster, which is the
    # paper's pool regime where prefill is the expensive path.  When set,
    # codec stages are modelled from the profile's measured throughputs
    # (V/s_enc, V/s_dec — Eq. 1) so sweeps are deterministic.
    prefill_tok_s: Optional[float] = None
    decode_tok_s: Optional[float] = None
    pool_fetch_overhead: float = 0.002   # pool RPC setup cost (s)
    store_capacity: int = 64 << 20       # wire bytes (remote/pool tier)
    store_block: int = 16
    # KV memory hierarchy (ISSUE 4).  None builds the default: pool mode
    # gets HBM -> DRAM -> remote (hot/dram capacities below; HBM/DRAM are
    # per-decode-worker, the remote pool tier is shared cluster-wide over
    # the runtime's BandwidthTrace); PD mode gets, per decode worker, a
    # single remote tier sharing that worker's ingress link (the pool
    # lives across the same wire the compressed KV crosses).  Pass an
    # explicit TierSpec list to override either (each worker then builds
    # its own private tiers from the specs; pass pre-built
    # :class:`~repro.serving.kvstore.KVTier` objects to share tiers).
    tiers: Optional[Sequence[TierSpec]] = None
    hot_tier_bytes: int = 4 << 20
    dram_tier_bytes: int = 16 << 20
    # PD cold path: what the decode arena is materialized from.  False
    # (default) keeps the prefill worker's exact cache — cold decode is
    # numerically identical to the pool scenario (token-exact vs the
    # pinned PR-1 fixture); the compressed payload still crosses the wire
    # byte-for-byte and is what later pool hits decode from, so the
    # profile's quality loss surfaces exactly where the pool path's does.
    # True injects the wire-restored KV instead (quality-faithful decode;
    # tokens then reflect the selected profile's loss immediately).
    pd_inject_restored: bool = False
    # Paged decode arena (DESIGN.md §12): the dense (n_slots, max_len)
    # cache becomes (num_pages, page_size, ...) pools with per-slot block
    # tables over a shared free pool — slot capacity is allocated page by
    # page on demand, and pool/PD hits whose stored strategy is
    # paged-eligible (symmetric per-token uniform int4/int8, see
    # ``repro.core.strategy.paged_eligible``) land as packed quantized
    # pages with NO materialized decompress on the TTFT critical path.
    # For token-exact parity with the dense arena, pick a ``page_size``
    # that divides ``seq + decode_tokens + 2``.
    paged: bool = False
    page_size: int = 16
    # Total pool pages (including the reserved scratch page 0).  None
    # sizes it worst-case-safe: n_slots * ceil(max_len / page_size) + 1.
    # Smaller values oversubscribe HBM (more slots than worst-case fit);
    # a slot that cannot grow raises ``ArenaOutOfPages``.
    arena_pages: Optional[int] = None
    # Speculative + lookahead decoding (DESIGN.md §15).  spec_k = 0
    # (default) keeps today's one-token-per-iteration arena decode,
    # bit-identical.  spec_k > 0 turns each decode iteration into a draft
    # phase (up to k proposed tokens per slot) + ONE masked multi-token
    # verify step; greedy verification keeps the token stream exact.
    spec_k: int = 0
    # Draft source: "ngram" = draft-free per-slot suffix-match lookahead
    # over prompt + generated tokens; "model" = two-model path (a draft
    # model's own dense arena proposes its greedy continuations).
    spec_kind: str = "ngram"
    # Controller-adaptive speculation length: the controller's per-route
    # accept-rate estimate picks each request's k from spec_candidates
    # (capped at spec_k); False applies spec_k uniformly.
    spec_adaptive: bool = False
    spec_candidates: Tuple[int, ...] = (0, 2, 4)

    @property
    def arena_max_len(self) -> int:
        """Arena row length.  The speculative path scatters up to spec_k
        extra in-flight KV rows past the last committed position, so the
        margin grows with the speculation width (spec_k = 0 keeps the
        historical seq + decode_tokens + 2 exactly)."""
        return self.seq + self.decode_tokens + 2 + self.spec_k


@dataclass
class ServedRequest:
    """Per-request outcome of the continuous runtime (the per-request
    analogue of :class:`~repro.serving.engine.ServedBatch`)."""

    rid: int
    workload: str
    slo_class: str
    text: str
    tokens: np.ndarray
    profile: str
    pool_hit: bool
    kv_bytes: int
    wire_bytes: int               # bytes this request moved over the wire
    arrival: float
    done: float
    ttft: float
    slot: int = -1                # arena slot that served the request
    # Placement: which (prefill worker -> decode worker) route served the
    # request ("p0->d0"; the slot id above is LOCAL to that decode worker).
    route: str = ""
    # Critical-path decomposition; sums exactly to jct.  Keys: queue,
    # prefill | comm+decompress (pool hit), decode, stall (time spent
    # waiting on the iteration's other stream), and — PD mode — compress,
    # wire_wait (queueing behind other transfers on the serialized wire),
    # comm, decompress, all on the request's critical path.
    breakdown: Dict[str, float] = field(default_factory=dict)
    # Off-critical-path cost of writing the compressed prefix to the pool
    # (compress + wire), charged to the background writer, not the request.
    # Always 0.0 in PD mode: there the transfer IS the critical path, and
    # the transferred bytes seed the decode-side pool for free.
    t_pool_write: float = 0.0
    # Which latency the SLO bounded ("ttft" | "jct"), the bound itself,
    # and whether it was violated — the bandit observed the SAME metric.
    slo_metric: str = "jct"
    t_slo: float = 0.0
    slo_violated: bool = False
    # Speculative-decode outcome (DESIGN.md §15): the k this request ran
    # with, verify steps taken, tokens committed by them, and the draft
    # offer/accept tallies behind the controller's accept-rate feedback.
    spec_k: int = 0
    verify_steps: int = 0
    spec_committed: int = 0
    drafts_offered: int = 0
    drafts_accepted: int = 0

    @property
    def jct(self) -> float:
        return self.done - self.arrival

    @property
    def tokens_per_step(self) -> float:
        """Mean committed tokens per verify step (1.0 when not run
        speculatively — every plain iteration commits one token)."""
        if self.verify_steps <= 0:
            return 1.0
        return self.spec_committed / self.verify_steps


@dataclass
class Slot:
    """Host-side bookkeeping for one occupied arena slot (the device-side
    state — cache row, position, live flag — lives in the owning
    :class:`DecodeWorker`'s arena arrays)."""

    req: Request
    idx: int                      # arena slot index (row in the cache pytree)
    toks: List[int]               # generated tokens (incl. first)
    pool_hit: bool
    profile: str
    wire_bytes: int
    breakdown: Dict[str, float]
    ttft: float
    route: str = ""               # placement route ("p0->d1")
    pool_write: float = 0.0       # off-path compress+write cost (misses)
    # Controller feedback deferred to _finish so the bandit observes the
    # request's realized critical-path latency (= breakdown sum = jct),
    # not the off-critical-path pool write.
    ctx: Optional[ServiceContext] = None
    decision: Optional[Decision] = None
    # Speculative decode state (DESIGN.md §15): this slot's draft budget
    # and its running verify/accept tallies.
    spec_k: int = 0
    verify_steps: int = 0
    spec_committed: int = 0
    drafts_offered: int = 0
    drafts_accepted: int = 0


@dataclass
class ModelHandle:
    """Shared mutable reference to the serving model.  Workers read
    (cfg, params) through this handle at call time, so a runtime-level
    swap — e.g. the tests pinning the session-cached reference model —
    reaches every worker without rebuilding them."""

    cfg: Any
    params: Any


class _Placed:
    """Device placement shared by both worker kinds.  ``device`` None (the
    default) leaves every array on JAX's default device; a given device
    holds the worker's params, arena and page pools, and arrays made on
    another device reach it only through an explicit ``jax.device_put``."""

    device: Optional[jax.Device] = None
    model: ModelHandle
    _params_src: Any = None
    _params_dev: Any = None

    @property
    def params(self):
        """The model's params on this worker's device, copied there once
        per runtime-level params swap."""
        src = self.model.params
        if self.device is None:
            return src
        if self._params_src is not src:
            self._params_dev = jax.device_put(src, self.device)
            self._params_src = src
        return self._params_dev

    def _put(self, tree):
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)


def codec_cost(cfg: RuntimeConfig, measured: float, nbytes: float,
               speed: float) -> float:
    """Codec stage cost: measured wall-clock, or — under the virtual
    clock — modelled from the profile's throughput (V/s, Eq. 1)."""
    if cfg.prefill_tok_s is None:
        return measured
    return 0.0 if speed == float("inf") else nbytes / speed


# ---------------------------------------------------------------------------
# Prefill worker
# ---------------------------------------------------------------------------
class PrefillWorker(_Placed):
    """One prefill engine of the cluster: runs real batch-1 prefills,
    selects/compresses the KV it ships, and carries the codec-cost model.
    Requests placed on the same worker within an iteration serialize on it
    (the caller threads the ``busy`` offset); distinct workers overlap."""

    def __init__(self, wid: int, model: ModelHandle, cfg: RuntimeConfig,
                 controller: Optional[ServiceAwareController] = None,
                 static_profile: Optional[Profile] = None,
                 device: Optional[jax.Device] = None):
        self.wid = wid
        self.name = f"p{wid}"
        self.model = model
        self.device = device
        self.cfg = cfg
        self.controller = controller
        self.static_profile = static_profile
        # EWMA of measured prefill wall-clock: the router's t_model
        # estimate when no virtual clock is configured.
        self._ewma_prefill: Optional[float] = None
        self._pre1 = None

    # ------------------------------------------------------------------
    def _prefill_fn(self):
        if self._pre1 is None:
            self._pre1, _, _ = _jitted_steps(
                self.model.cfg.name, self.cfg.seq, 1, self.cfg.arena_max_len)
        return self._pre1

    def expected_prefill_s(self, ctx_tokens: int) -> float:
        """The router's estimate of this worker's prefill time: exact
        under the virtual clock, EWMA of measured wall-clock otherwise."""
        if self.cfg.prefill_tok_s:
            return ctx_tokens / self.cfg.prefill_tok_s
        return self._ewma_prefill if self._ewma_prefill is not None else 0.0

    # ------------------------------------------------------------------
    def prefill(self, req: Request, tokens: np.ndarray):
        """Real batch-1 prefill.  Returns ``(caches, first_token,
        t_prefill)`` with ``t_prefill`` under the configured cost model."""
        pre1 = self._prefill_fn()
        with tracing.timed("prefill", rid=req.rid) as sp:
            logits, caches = pre1(self.params, {"tokens": tokens[None, :]})
            # lint: sync-ok(measures real prefill wall-clock for the EWMA model)
            jax.block_until_ready(logits)
            if sp:
                sp.add(tokens=len(tokens))
        t_wall = sp.seconds
        t_prefill = (req.ctx_tokens / self.cfg.prefill_tok_s
                     if self.cfg.prefill_tok_s else t_wall)
        self._ewma_prefill = t_wall if self._ewma_prefill is None \
            else 0.7 * self._ewma_prefill + 0.3 * t_wall
        # lint: sync-ok(one first-token pull per prefill seeds the decode slot)
        first = int(np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))[0])
        return caches, first, t_prefill

    # ------------------------------------------------------------------
    def select_and_compress(self, req: Request, caches, t_prefill: float,
                            bandwidth: float, slo_default: str,
                            route: str = ""):
        """Controller decision + real compression of the prefix KV.
        ``bandwidth`` is the selecting route's goodput estimate (per-link
        in a cluster) and ``route`` its identity, so the controller's
        residual bandit learns each link's drift separately.  Returns
        ``(comp, ctx, decision, profile, t_compress)``."""
        kv = extract_kv(self.model.cfg, caches, 0, upto=self.cfg.seq)
        # Serial decode-stream time under the virtual clock feeds the
        # controller's speculation-length choice (DESIGN.md §15); 0 when
        # wall-clock-measured (the k-selection then ranks on modelled
        # throughput alone).
        t_decode = (req.out_tokens / self.cfg.decode_tok_s
                    if self.cfg.decode_tok_s else 0.0)
        ctx = ServiceContext(
            workload=req.workload, bandwidth=bandwidth,
            t_slo=req.t_slo, q_min=req.q_min, t_model=t_prefill,
            kv_bytes=kv.nbytes_wire(),
            slo_metric=req.resolved_slo_metric(slo_default),
            route=route, decode_time=t_decode)
        with tracing.span("select") as sp:
            profile, decision = _select_profile(self.controller,
                                                self.static_profile, ctx)
            if sp:
                sp.add(profile=profile.strategy.short_name())
        comps, _, t_wall = compress_kvs(profile.strategy, [kv])
        t_compress = codec_cost(self.cfg, t_wall, kv.nbytes_wire(),
                                profile.s_enc)
        return comps[0], ctx, decision, profile, t_compress


# ---------------------------------------------------------------------------
# Decode worker
# ---------------------------------------------------------------------------
class DecodeWorker(_Placed):
    """One decode engine of the cluster: a fixed-capacity slot arena (ONE
    cache pytree, leading axis ``n_slots``), a LIFO local slot-id pool,
    and the worker's decode-side KV tier hierarchy."""

    def __init__(self, wid: int, model: ModelHandle, cfg: RuntimeConfig,
                 n_slots: int, store: Any,
                 device: Optional[jax.Device] = None):
        self.wid = wid
        self.name = f"d{wid}"
        self.model = model
        self.device = device
        self.cfg = cfg
        self.n_slots = n_slots
        self.store = store
        self.max_len = cfg.arena_max_len
        self.slots: Dict[int, Slot] = {}
        # LIFO so a hot slot's cache row is reused first (same recycling
        # discipline the scheduler used when it owned the slot ids).
        self.free_slots: List[int] = list(range(n_slots))[::-1]
        self._dec_arena = None
        self._arena: Any = None          # cache pytree, leading axis n_slots
        self._positions = np.zeros(n_slots, np.int32)  # next write pos
        self._last_tok = np.zeros(n_slots, np.int32)   # last emitted tok
        self.decode_steps = 0            # lifetime arena decode calls
        # Paged-arena state (cfg.paged; DESIGN.md §12).  The fp pool
        # replaces the dense arena in self._arena; the parallel quant
        # pools hold packed pages for fused-dequant decode, valid per
        # slot below its _quant_len watermark.
        self.page_table: Optional[PageTable] = None
        self._qcodes: Any = None
        self._qscales: Any = None
        self._quant_len = np.zeros(n_slots, np.int32)
        # Speculative decode state (DESIGN.md §15): the draft proposer
        # (built lazily when a speculative slot first lands) and the
        # worker-lifetime accept tallies behind the benchmark's
        # tokens-per-step metric.
        self._draft: Any = None
        self._verify_fns: Dict[int, Any] = {}   # width -> jitted verify
        self.verify_steps = 0
        self.spec_committed = 0

    @property
    def _pps(self) -> int:
        """Block-table row length: pages per worst-case slot."""
        return -(-self.max_len // self.cfg.page_size)

    # ------------------------------------------------------------------
    @property
    def free_slot_count(self) -> int:
        return len(self.free_slots)

    @property
    def occupancy(self) -> int:
        return len(self.slots)

    # ------------------------------------------------------------------
    def ensure_arena(self):
        if self._arena is None:
            from repro.models.transformer import init_cache, plan_stack
            plan = plan_stack(self.model.cfg)
            if any(s.kind != "attn"
                   for s in plan.prefix_specs + plan.period_specs):
                raise NotImplementedError(
                    "slot arena masking assumes attention-only caches "
                    "(SSM states advance unmasked)")
            # Allocated on the worker's device directly: never staged
            # through the default device, which may be another worker's.
            with jax.default_device(self.device):
                if self.cfg.paged:
                    num_pages = (self.cfg.arena_pages
                                 or self.n_slots * self._pps + 1)
                    self.page_table = PageTable(num_pages,
                                                self.cfg.page_size)
                    # Per-channel scale layout in the sim pools (group=1):
                    # any strategy group maps onto it by broadcasting its
                    # group scale, so one pool serves every eligible
                    # profile.
                    self._arena, self._qcodes, self._qscales = (
                        init_paged_pools(self.model.cfg, num_pages,
                                         self.cfg.page_size, group=1))
                else:
                    self._arena = init_cache(self.model.cfg, self.n_slots,
                                             self.max_len)
        return self._arena

    def _arena_fn(self):
        if self._dec_arena is None:
            if self.cfg.paged:
                self._dec_arena, _ = _paged_steps(self.model.cfg.name,
                                                  self.cfg.page_size)
            else:
                _, _, self._dec_arena = _jitted_steps(
                    self.model.cfg.name, self.cfg.seq, self.n_slots,
                    self.max_len)
        return self._dec_arena

    # ------------------------------------------------------------------
    def _block_tables(self) -> np.ndarray:
        bt = np.zeros((self.n_slots, self._pps), np.int32)
        for s, owned in self.page_table.pages.items():
            bt[s, :len(owned)] = owned
        return bt

    def copy_from_caches(self, caches, idx: int) -> None:
        """Materialize arena row ``idx`` from a prefill worker's batch-1
        cache (the cold path's slot hand-off; the cache moves from the
        prefill worker's device to this one by ``device_put``).  No byte
        crosses from the host."""
        with tracing.span("inject") as sp:
            self.ensure_arena()
            caches = self._put(caches)
            if self.cfg.paged:
                self.page_table.ensure(idx, self.cfg.seq)
                row = self.page_table.block_row(idx, self._pps)
                self._arena = copy_cache_slot_paged(
                    self.model.cfg, self._arena, caches, row,
                    self.cfg.page_size)
                self._quant_len[idx] = 0
            else:
                self._arena = copy_cache_slot(self.model.cfg, self._arena,
                                              caches, idx)
            if sp:
                sp.add(bytes=0)

    def inject_restored(self, kv, idx: int) -> None:
        """Materialize arena row ``idx`` from a wire-restored KV (pushed
        from the host in the arena's dtype)."""
        with tracing.span("inject") as sp:
            self.ensure_arena()
            if self.cfg.paged:
                self.page_table.ensure(idx, kv.seq)
                row = self.page_table.block_row(idx, self._pps)
                self._arena = inject_kv_paged(self.model.cfg, self._arena,
                                              row, kv, self.cfg.page_size)
                self._quant_len[idx] = 0
            else:
                self._arena = inject_kv(self.model.cfg, self._arena, idx,
                                        kv)
            if sp:
                leaf = jax.tree_util.tree_leaves(self._arena)[0]
                sp.add(bytes=(kv.k.size + kv.v.size) * leaf.dtype.itemsize)

    def fetch_entry(self, entry, idx: int) -> Tuple[int, float]:
        """Land a stored pool entry in arena slot ``idx``.  Returns
        ``(first_token, t_decompress)``.

        Paged arena + paged-eligible stored strategy: the packed codes
        and fp16 group scales scatter STRAIGHT into the quantized page
        pools — no fp16 materialization, so the decompress stage leaves
        the TTFT critical path (the fused dequant runs inside decode
        attention; under the virtual clock the remaining adapter cost
        models as V/inf = 0).  Everything else decompresses and injects
        fp16 pages/rows as before.  Cache injection is host-side
        bookkeeping of the miniature (the cold path's equivalent writes
        happen inside prefill), so it is not billed to the virtual
        clock."""
        comp, first, s_dec = entry.payload
        if (self.cfg.paged and isinstance(comp, CompressedKV)
                and paged_eligible(comp.strategy, head_dim=comp.shape[3])):
            with tracing.timed("restore") as sp:
                (kc, ks), (vc, vs) = quant_entry_arrays(comp)
                self.ensure_arena()
                seq = comp.shape[2]
                self.page_table.ensure(idx, seq)
                row = self.page_table.block_row(idx, self._pps)
                self._qcodes, self._qscales = inject_quant_pages(
                    self.model.cfg, self._qcodes, self._qscales, row,
                    kc, ks, vc, vs, seq, self.cfg.page_size)
                self._quant_len[idx] = seq
                if sp:
                    sp.add(bytes=kc.nbytes + ks.nbytes + vc.nbytes + vs.nbytes)
            return int(first), codec_cost(self.cfg, sp.seconds,
                                          entry.kv_bytes, float("inf"))
        restored, t_wall = decompress_kvs([comp])
        t_decompress = codec_cost(self.cfg, t_wall, entry.kv_bytes, s_dec)
        self.inject_restored(restored[0], idx)
        return int(first), t_decompress

    # ------------------------------------------------------------------
    def draft(self):
        """The worker's draft proposer (cfg.spec_kind), built lazily."""
        if self._draft is None:
            from repro.serving.speculative import ModelDraft, NGramDraft
            if self.cfg.spec_kind == "model":
                self._draft = ModelDraft(self.model, self.cfg.seq,
                                         self.n_slots, self.max_len)
            else:
                self._draft = NGramDraft()
        return self._draft

    def _verify_fn(self, width: int):
        """Jitted multi-token verify for ``width`` (one compile per
        speculation width; the per-slot accept length stays traced)."""
        fn = self._verify_fns.get(width)
        if fn is None:
            from repro.core.quality import _paged_verify_steps, _verify_steps
            if self.cfg.paged:
                fn = _paged_verify_steps(self.model.cfg.name,
                                         self.cfg.page_size, width)
            else:
                fn = _verify_steps(self.model.cfg.name, self.max_len, width)
            self._verify_fns[width] = fn
        return fn

    def occupy(self, slot: Slot, first: int,
               prompt: Optional[Sequence[int]] = None) -> None:
        self.slots[slot.req.rid] = slot
        self._positions[slot.idx] = self.cfg.seq
        self._last_tok[slot.idx] = first
        if slot.spec_k > 0 and prompt is not None:
            self.draft().start(slot.idx, slot.req.rid, prompt, first)

    def release(self, slot: Slot) -> None:
        self.free_slots.append(slot.idx)
        del self.slots[slot.req.rid]
        if self.cfg.paged and self.page_table is not None:
            self.page_table.release(slot.idx)
            self._quant_len[slot.idx] = 0
        if slot.spec_k > 0 and self._draft is not None:
            self._draft.stop(slot.idx, slot.req.rid)

    # ------------------------------------------------------------------
    def decode_iteration(self, active: List[Slot]) -> float:
        """Advance every slot in ``active`` with a SINGLE masked jitted
        arena call.  Without speculation (or when no slot has a draft this
        round) that is the historical one-token decode, bit-identical to
        pre-speculative builds.  With drafts it is ONE multi-token verify
        step: each slot commits the longest draft prefix the target would
        have emitted plus the bonus token (DESIGN.md §15) — token-exact
        with sequential decode, 1..width tokens per slot per iteration.
        Returns the measured wall seconds."""
        proposals: Dict[int, List[int]] = {}
        if self.cfg.spec_k > 0:
            spec = [s for s in active if s.spec_k > 0]
            if spec:
                items = [(s.idx, s.req.rid, int(self._last_tok[s.idx]),
                          int(self._positions[s.idx])) for s in spec]
                budgets = {s.idx: s.spec_k for s in spec}
                proposals = {i: d for i, d in
                             self.draft().propose_all(items, budgets).items()
                             if d}
        if proposals:
            return self._verify_iteration(active, proposals)
        mask = np.zeros(self.n_slots, bool)
        for slot in active:
            mask[slot.idx] = True
        dec = self._arena_fn()
        self.ensure_arena()
        if self.cfg.paged:
            # Grow each live slot to cover this step's write position —
            # the on-demand allocation that replaces worst-case sizing.
            for slot in active:
                self.page_table.ensure(slot.idx,
                                       int(self._positions[slot.idx]) + 1)
        with tracing.timed("decode_step") as sp:
            if self.cfg.paged:
                nxt, self._arena = dec(
                    self.params, self._arena, self._qcodes,
                    self._qscales, jnp.asarray(self._block_tables()),
                    jnp.asarray(self._quant_len),
                    jnp.asarray(self._last_tok[:, None]),
                    jnp.asarray(self._positions), jnp.asarray(mask))
            else:
                nxt, self._arena = dec(
                    self.params, self._arena,
                    jnp.asarray(self._last_tok[:, None]),
                    jnp.asarray(self._positions), jnp.asarray(mask))
            with tracing.span("token_pull"):
                # lint: sync-ok(the step's single sanctioned sync - one batched pull)
                nxt = np.asarray(nxt)
            if sp:
                sp.add(live=len(active))
        for slot in active:
            t = int(nxt[slot.idx])
            slot.toks.append(t)
            self._last_tok[slot.idx] = t
            self._positions[slot.idx] += 1
            if slot.spec_k > 0 and self._draft is not None:
                self._draft.commit(slot.idx, slot.req.rid, [t])
        self.decode_steps += 1
        return sp.seconds

    def _verify_iteration(self, active: List[Slot],
                          proposals: Dict[int, List[int]]) -> float:
        """One masked multi-token verify step over the arena.  Every
        active slot rides along at its own draft length (no drafts = a
        plain one-token step inside the wide call); rejected draft
        positions never advance a slot and — paged — their over-ensured
        tail pages are rolled back before the pages can leak."""
        from repro.serving.speculative import accept_length
        width = max(len(d) for d in proposals.values()) + 1
        mask = np.zeros(self.n_slots, bool)
        toks = np.zeros((self.n_slots, width), np.int32)
        for slot in active:
            mask[slot.idx] = True
            toks[slot.idx, 0] = self._last_tok[slot.idx]
            for j, d in enumerate(proposals.get(slot.idx, [])):
                toks[slot.idx, 1 + j] = d
        fn = self._verify_fn(width)
        self.ensure_arena()
        if self.cfg.paged:
            # Ensure through the worst-case commit (all drafts accepted);
            # the rejected tail is released again right after the verify.
            for slot in active:
                need = (int(self._positions[slot.idx]) + 1
                        + len(proposals.get(slot.idx, [])))
                self.page_table.ensure(slot.idx, need)
        with tracing.timed("decode_step") as sp:
            if self.cfg.paged:
                out, self._arena = fn(
                    self.params, self._arena, self._qcodes,
                    self._qscales, jnp.asarray(self._block_tables()),
                    jnp.asarray(self._quant_len), jnp.asarray(toks),
                    jnp.asarray(self._positions), jnp.asarray(mask))
            else:
                out, self._arena = fn(
                    self.params, self._arena, jnp.asarray(toks),
                    jnp.asarray(self._positions), jnp.asarray(mask))
            with tracing.span("token_pull"):
                # lint: sync-ok(the step's single sanctioned sync - one batched pull)
                out = np.asarray(out)
            if sp:
                sp.add(live=len(active))
        for slot in active:
            drafts = proposals.get(slot.idx, [])
            row = out[slot.idx]
            a = accept_length(drafts, row)
            needed = slot.req.out_tokens + 1 - len(slot.toks)
            c = min(a + 1, max(needed, 1))
            committed = [int(row[j]) for j in range(c)]
            slot.toks.extend(committed)
            self._last_tok[slot.idx] = committed[-1]
            self._positions[slot.idx] += c
            slot.verify_steps += 1
            slot.spec_committed += c
            slot.drafts_offered += len(drafts)
            slot.drafts_accepted += min(a, c - 1)
            self.spec_committed += c
            if slot.spec_k > 0 and self._draft is not None:
                self._draft.commit(slot.idx, slot.req.rid, committed)
            if self.cfg.paged and drafts:
                self.page_table.release_tail(
                    slot.idx, int(self._positions[slot.idx]))
        self.decode_steps += 1
        self.verify_steps += 1
        return sp.seconds
