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
  arena in one layout (:mod:`repro.serving.arena`, advanced by a single
  masked jitted step per iteration), its own local slot-id pool, and its
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
from repro.core.kvcache import state_nbytes
from repro.core.pipeline import CompressedKV, CompressionPipeline
from repro.core.profiles import Profile
from repro.core.quality import DeviceKV, _jitted_steps
from repro.core.strategy import StrategyConfig
from repro.models.layers import COMPUTE_DTYPE
from repro.serving import tracing
from repro.serving.arena import DenseArena, PagedArena
from repro.serving.kvstore import TierSpec
from repro.serving.request import Request
from repro.serving.speculative import accept_length

# The arena holds keys and values in the compute type.
KV_ITEMSIZE = np.dtype(COMPUTE_DTYPE).itemsize


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
    """Compress each KV prefix for the wire: host :class:`KVCache`s, or
    :class:`DeviceKV`s pulled off the device inside this stage.  Returns
    ``(payloads, wire_bytes, measured_seconds)``."""
    pipe = CompressionPipeline(strategy)
    with tracing.timed("compress") as sp:
        comps = [pipe.compress(kv) for kv in kvs]
        if sp:
            sp.add(kv_bytes=sum(kv.nbytes_wire() for kv in kvs),
                   wire_bytes=sum(c.total_bytes() for c in comps),
                   state_bytes=sum(state_nbytes(c.state) for c in comps))
    return comps, sum(c.total_bytes() for c in comps), sp.seconds


def decompress_kvs(comps: Sequence[CompressedKV], fp16: bool = False
                   ) -> Tuple[List[Any], float]:
    """Restore wire payloads to KV.  Returns ``(kvs, measured_seconds)``.
    ``fp16`` for an arena's injection: identity payloads then stay fp16
    views (:meth:`CompressionPipeline.decompress`)."""
    with tracing.timed("restore") as sp:
        kvs = [CompressionPipeline(c.strategy).decompress(c, fp16)
               for c in comps]
        if sp:
            sp.add(bytes=sum(kv.k.nbytes + kv.v.nbytes for kv in kvs))
    return kvs, sp.seconds


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
        ``(comp, ctx, decision, profile, t_compress)``.  The profile is
        chosen from the KV's shape alone; the KV leaves the device inside
        the compress stage, in the form the strategy needs (the identity
        hand-off pulls fp16 made on the device)."""
        kv = DeviceKV(self.model.cfg, caches, 0, upto=self.cfg.seq)
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
    """One decode engine of the cluster: a fixed-capacity slot arena in
    one layout (:mod:`repro.serving.arena`), a LIFO local slot-id pool,
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
        if cfg.paged:
            self.layout = PagedArena(model, n_slots, self.max_len,
                                     cfg.page_size, cfg.arena_pages, device)
        else:
            self.layout = DenseArena(model, n_slots, self.max_len, cfg.seq,
                                     device)
        self._positions = np.zeros(n_slots, np.int32)  # next write pos
        self._last_tok = np.zeros(n_slots, np.int32)   # last emitted tok
        self.decode_steps = 0            # lifetime arena decode calls
        # Speculative decode state (DESIGN.md §15): the draft proposer
        # (built lazily when a speculative slot first lands) and the
        # worker-lifetime accept tallies behind the benchmark's
        # tokens-per-step metric.
        self._draft: Any = None
        self.verify_steps = 0
        self.spec_committed = 0

    @property
    def _arena(self):
        """The layout's device cache pytree (leading axis ``n_slots``, or
        ``num_pages`` for the paged layout)."""
        return self.layout.cache

    @_arena.setter
    def _arena(self, cache) -> None:
        self.layout.cache = cache

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
            from repro.models.transformer import plan_stack
            plan = plan_stack(self.model.cfg)
            if ((not self.layout.holds_state or self.cfg.spec_k > 0)
                    and any(s.kind != "attn"
                            for s in plan.prefix_specs + plan.period_specs)):
                raise NotImplementedError(
                    "SSM layers run in the dense arena's one-token step "
                    "only: the paged arena and the speculative verify step "
                    "hold no recurrent state per slot")
            self.layout.allocate()
        return self._arena

    def _arena_fn(self):
        """The layout's jitted step, which :meth:`decode_iteration` calls
        with the layout's operands."""
        return self.layout.program()

    # ------------------------------------------------------------------
    def copy_from_caches(self, caches, idx: int) -> None:
        """Materialize arena row ``idx`` from a prefill worker's batch-1
        cache (the cold path's slot hand-off; the cache moves from the
        prefill worker's device to this one by ``device_put``).  No byte
        crosses from the host."""
        with tracing.span("inject") as sp:
            self.ensure_arena()
            self.layout.land_prefill(self._put(caches), idx, self.cfg.seq)
            if sp:
                sp.add(bytes=0)

    def inject_restored(self, kv, idx: int) -> None:
        """Materialize arena row ``idx`` from a wire-restored KV and its
        recurrent state, if any: K and V are pushed as held (fp16 for an
        identity restore) and cast on the device (span ``state_inject``
        inside ``inject``)."""
        with tracing.span("inject") as sp:
            self.ensure_arena()
            self.layout.inject(kv, idx)
            if sp:
                n_state = state_nbytes(kv.state)
                sp.add(bytes=(kv.k.size + kv.v.size) * KV_ITEMSIZE + n_state,
                       state_bytes=n_state)

    def fetch_entry(self, entry, idx: int) -> Tuple[int, float]:
        """Land a stored pool entry in arena slot ``idx``.  Returns
        ``(first_token, t_decompress)``.

        A layout that fuses dequant (paged) takes a paged-eligible stored
        strategy's packed codes and fp16 group scales STRAIGHT into its
        quantized page pools — no fp16 materialization, so the decompress
        stage leaves the TTFT critical path (the fused dequant runs inside
        the decode step; under the virtual clock the remaining adapter
        cost models as V/inf = 0).  Everything else decompresses (an
        identity payload as fp16) and injects.  Cache injection is
        host-side bookkeeping of the miniature (the cold path's equivalent
        writes happen inside prefill), so it is not billed to the virtual
        clock."""
        comp, first, s_dec = entry.payload
        if (isinstance(comp, CompressedKV)
                and self.layout.fuses(comp.strategy, comp.shape[3])):
            with tracing.timed("restore") as sp:
                self.ensure_arena()
                nbytes = self.layout.land_pages(comp, idx)
                if sp:
                    sp.add(bytes=nbytes)
            return int(first), codec_cost(self.cfg, sp.seconds,
                                          entry.kv_bytes, float("inf"))
        restored, t_wall = decompress_kvs([comp], fp16=True)
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
        self.layout.release(slot.idx)
        if slot.spec_k > 0 and self._draft is not None:
            self._draft.stop(slot.idx, slot.req.rid)

    # ------------------------------------------------------------------
    def decode_iteration(self, active: List[Slot]) -> float:
        """Advance every slot in ``active`` with a SINGLE masked jitted
        arena step at width ``w`` = 1 + the longest draft this round
        (DESIGN.md §15).  Without drafts that is the one-token decode.
        With drafts each slot rides along at its own draft length and
        commits the longest draft prefix the target would have emitted
        plus the bonus token: 1..w tokens per slot per iteration.
        Rejected draft positions never advance a slot, and a layout with
        pages releases the tail it grew for them.  Returns the measured
        wall seconds."""
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
        width = 1 + max(map(len, proposals.values()), default=0)
        mask = np.zeros(self.n_slots, bool)
        toks = np.zeros((self.n_slots, width), np.int32)
        toks[:, 0] = self._last_tok
        step = self._arena_fn()
        self.ensure_arena()
        for slot in active:
            drafts = proposals.get(slot.idx, [])
            mask[slot.idx] = True
            toks[slot.idx, 1:1 + len(drafts)] = drafts
            # Grow the slot through its worst-case commit (every draft
            # accepted): on-demand allocation in place of worst-case sizing.
            self.layout.grow(slot.idx, int(self._positions[slot.idx]) + 1
                             + len(drafts))
        with tracing.timed("decode_step") as sp:
            out, self._arena = step(
                self.params, self._arena, *self.layout.operands(),
                jnp.asarray(toks), jnp.asarray(self._positions),
                jnp.asarray(mask))
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
            committed = [int(t) for t in row[:c]]
            slot.toks.extend(committed)
            self._last_tok[slot.idx] = committed[-1]
            self._positions[slot.idx] += c
            if proposals:
                slot.verify_steps += 1
                slot.spec_committed += c
                slot.drafts_offered += len(drafts)
                slot.drafts_accepted += min(a, c - 1)
                self.spec_committed += c
            if slot.spec_k > 0 and self._draft is not None:
                self._draft.commit(slot.idx, slot.req.rid, committed)
            if drafts:
                self.layout.release(slot.idx,
                                    upto=int(self._positions[slot.idx]))
        self.decode_steps += 1
        self.verify_steps += bool(proposals)
        return sp.seconds
