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
from functools import lru_cache, partial
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.kvcache import KVCache, handoff_bytes, state_nbytes
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
# Cache <-> KVCache conversion (attention layers and SSM state, dense stacks)
# ---------------------------------------------------------------------------
def _layers_of(cfg, caches, kind: str):
    """``(prefix, blocks, n_blocks)``: the cache leaves of the layers of
    ``kind`` ("attn" | "ssm"), prefix layers and one period's layers (each
    stacked over the scanned blocks), in layer order."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    prefix = tuple(caches["prefix"][f"layer{i}"]
                   for i, s in enumerate(plan.prefix_specs) if s.kind == kind)
    blocks = tuple(caches["blocks"][f"layer{j}"]
                   for j, s in enumerate(plan.period_specs) if s.kind == kind)
    return prefix, blocks, plan.n_blocks


def _rows(prefix, blocks, key: str, batch_idx, upto=None):
    """One batch element's ``key`` leaf of every layer, stacked in layer
    order: the prefix layers, then the scanned blocks block by block."""
    layers = [jax.lax.dynamic_index_in_dim(c[key], batch_idx, 0,
                                           False)[None, :upto]
              for c in prefix]
    if blocks:
        # block leaves (n_blocks, B, ...)
        per = jnp.stack([jax.lax.dynamic_index_in_dim(
            c[key], batch_idx, 1, False)[:, :upto] for c in blocks], 1)
        layers.append(per.reshape(-1, *per.shape[2:]))
    return jnp.concatenate(layers)


@partial(jax.jit, static_argnames=("upto", "dtype"))
def _gather_kv(prefix, blocks, states, batch_idx, upto: int, dtype):
    """One batch element's hand-off: its attention K and V as ONE (2, L, H,
    S, D) array in ``dtype`` (K then V, layer order), and the SSM layers'
    ``states`` (``(prefix, blocks)`` of their cache leaves) as
    ``{"ssm", "conv"}`` arrays in their own types, or None."""
    kv = jnp.stack([_rows(prefix, blocks, t, batch_idx, upto)
                    .swapaxes(1, 2).astype(dtype) for t in ("k", "v")])
    if not (states[0] or states[1]):
        return kv, None
    return kv, {t: _rows(*states, t, batch_idx) for t in ("ssm", "conv")}


class DeviceKV:
    """One batch element's hand-off, still in the device cache pytree that
    a prefill wrote: the source a :class:`CompressionPipeline` pulls from.
    Its shape and wire size are known without a pull; each pull is one
    jitted gather and one device->host transfer of each array it made
    (span ``kv_pull``: ``bytes`` that crossed, ``state_bytes`` of them
    the SSM layers' state, ``transfers``, and ``device_cast`` 1 where the
    device made the wire payload, 0 where the host widens).  ``state``
    holds the recurrent state of the last pull (None before one, and for
    attention-only models)."""

    def __init__(self, cfg, caches, batch_idx: int, upto: int):
        self._prefix, self._blocks, n_blocks = _layers_of(cfg, caches,
                                                          "attn")
        self._states = _layers_of(cfg, caches, "ssm")[:2]
        self._batch_idx = batch_idx
        self._upto = upto
        leaf = (self._prefix + self._blocks)[0]["k"]
        n_layers = len(self._prefix) + len(self._blocks) * n_blocks
        self.shape = (n_layers, leaf.shape[-2], upto, leaf.shape[-1])
        self.cache_dtype = np.dtype(leaf.dtype)
        self._wire_bytes = handoff_bytes(cfg, upto)
        self.state: Optional[Dict[str, np.ndarray]] = None

    def nbytes_wire(self) -> int:
        """Bytes of the uncompressed payload on the wire, as
        :meth:`KVCache.nbytes_wire`: K and V in logical bf16, plus the
        state as held."""
        return self._wire_bytes

    def _pull(self, dtype, device_cast: int) -> np.ndarray:
        from repro.serving import tracing

        with tracing.span("kv_pull") as sp:
            # lint: sync-ok(the hand-off leaves the device here, once per request)
            kv, state = jax.device_get(_gather_kv(
                self._prefix, self._blocks, self._states, self._batch_idx,
                self._upto, np.dtype(dtype)))
            if sp:
                n_state = state_nbytes(state)
                sp.add(bytes=kv.nbytes + n_state, state_bytes=n_state,
                       transfers=1 + len(state or ()),
                       device_cast=device_cast)
        self.state = state
        return kv

    def fp16(self) -> np.ndarray:
        """K then V as one C-ordered (2, L, H, S, D) float16 host array,
        cast on the device: the identity hand-off's wire payload (the
        state comes with it, in :attr:`state`)."""
        return self._pull(np.float16, device_cast=1)

    def host(self) -> KVCache:
        """The float32 :class:`KVCache`: pulled in the cache dtype and
        widened on the host (exact from bf16), with the state."""
        kv = self._pull(self.cache_dtype, device_cast=0).astype(np.float32)
        return KVCache(kv[0], kv[1], self.state)


def extract_kv(cfg, caches, batch_idx: int, upto: int) -> KVCache:
    """Pull one batch element's attention KV as (L, H, S, D) float32
    numpy, in one device->host transfer (span ``kv_pull``)."""
    return DeviceKV(cfg, caches, batch_idx, upto).host()


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


def _kv_pair(kv: KVCache) -> np.ndarray:
    """K then V as one C-ordered (2, L, H, S, D) array: a view where they
    are the two halves of one buffer, as a restored identity payload's
    are, else a copy."""
    flat = kv.k.base
    if (isinstance(flat, np.ndarray) and flat.ndim == 1
            and flat.size == 2 * kv.k.size and flat.flags.c_contiguous
            and kv.k.flags.c_contiguous and kv.v.flags.c_contiguous
            and kv.k.ctypes.data == flat.ctypes.data
            and kv.v.ctypes.data == flat.ctypes.data + kv.k.nbytes):
        return flat.reshape((2,) + kv.shape)
    return np.stack([kv.k, kv.v])


@jax.jit
def _scatter_kv(prefix, blocks, kv, slot):
    """Row ``slot`` of every attention layer's K and V leaves set from
    ``kv`` ((2, L, H, S, D), K then V in :func:`_rows` order: the prefix
    layers, then each period layer across the blocks), cast to the
    leaves' dtype.  ``slot`` is traced: one program serves every row."""
    rows = kv.swapaxes(2, 3)                       # (2, L, S, H, D)
    n_prefix, n = len(prefix), len(blocks)

    def put(buf, upd, axis):
        start = [0] * buf.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(
            buf, jnp.expand_dims(upd.astype(buf.dtype), axis), tuple(start))

    new_prefix = tuple({t: put(c[t], rows[i_t, i], 0)
                        for i_t, t in enumerate(("k", "v"))}
                       for i, c in enumerate(prefix))
    new_blocks = tuple({t: put(c[t], rows[i_t, n_prefix + j::n], 1)
                        for i_t, t in enumerate(("k", "v"))}
                       for j, c in enumerate(blocks))
    return new_prefix, new_blocks


def _with_layers(cfg, caches, kind: str, new_prefix, new_blocks):
    """``caches`` with the leaves of its layers of ``kind`` replaced by
    ``(new_prefix, new_blocks)``, in :func:`_layers_of` order."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    out = {"prefix": dict(caches["prefix"]), "blocks": dict(caches["blocks"])}
    for part, specs, new in (("prefix", plan.prefix_specs, new_prefix),
                             ("blocks", plan.period_specs, new_blocks)):
        names = [f"layer{i}" for i, s in enumerate(specs) if s.kind == kind]
        out[part].update(zip(names, new))
    return out


def inject_kv(cfg, caches, batch_idx: int, kv: KVCache):
    """Write a (possibly lossy) KVCache back into the cache pytree, on the
    device that holds it: the attention layers' K and V, and the SSM
    layers' state where the hand-off carries one (:func:`inject_state`).

    K and V cross as they are held (fp16 for a restored identity
    payload, float32 otherwise), in one push, and one jitted program
    casts them to the cache dtype and writes every layer's row."""
    prefix, blocks, _ = _layers_of(cfg, caches, "attn")
    where = (prefix + blocks)[0]["k"].sharding
    new_prefix, new_blocks = _scatter_kv(
        prefix, blocks, jax.device_put(_kv_pair(kv), where),
        jnp.asarray(batch_idx, jnp.int32))
    caches = _with_layers(cfg, caches, "attn", new_prefix, new_blocks)
    if kv.state is not None:
        caches = inject_state(cfg, caches, batch_idx, kv.state)
    return caches


@jax.jit
def _set_state(prefix, blocks, slot, state):
    """Row ``slot`` of every SSM layer's state leaves set from ``state``
    (``{"ssm", "conv"}``, one row per SSM layer in :func:`_rows` order)."""
    n = len(blocks)
    new_prefix = tuple({t: c[t].at[slot].set(state[t][i].astype(c[t].dtype))
                        for t in c}
                       for i, c in enumerate(prefix))
    new_blocks = tuple({t: c[t].at[:, slot].set(
        state[t][len(prefix) + j::n].astype(c[t].dtype)) for t in c}
        for j, c in enumerate(blocks))
    return new_prefix, new_blocks


def inject_state(cfg, caches, batch_idx: int, state):
    """Write one request's recurrent state (:attr:`KVCache.state`) into row
    ``batch_idx`` of every SSM layer of the cache pytree, pushed from the
    host in the types it is held in (span ``state_inject``)."""
    from repro.serving import tracing

    with tracing.span("state_inject"):
        prefix, blocks, _ = _layers_of(cfg, caches, "ssm")
        where = jax.tree_util.tree_leaves(caches)[0].sharding
        new_prefix, new_blocks = _set_state(
            prefix, blocks, jnp.asarray(batch_idx, jnp.int32),
            jax.device_put(dict(state), where))
        return _with_layers(cfg, caches, "ssm", new_prefix, new_blocks)


def _hold_parked_state(cfg, new, old, mask):
    """The decoded cache with every SSM layer's state of a parked row
    (``mask`` False) kept as it was.  A parked row's attention write is
    inert at the scratch position, but its recurrent state would advance
    on the row's dummy token.  Attention-only models come back as decoded."""
    from repro.models.transformer import plan_stack

    plan = plan_stack(cfg)
    out = {"prefix": dict(new["prefix"]), "blocks": dict(new["blocks"])}
    for part, specs, axis in (("prefix", plan.prefix_specs, 0),
                              ("blocks", plan.period_specs, 1)):
        for i, spec in enumerate(specs):
            if spec.kind != "ssm":
                continue
            name = f"layer{i}"
            out[part][name] = {
                t: jnp.where(mask.reshape((1,) * axis + (-1,)
                                          + (1,) * (a.ndim - axis - 1)),
                             a, old[part][name][t])
                for t, a in new[part][name].items()}
    return out


# ---------------------------------------------------------------------------
# Quality evaluation
# ---------------------------------------------------------------------------
@lru_cache(maxsize=8)
def _jitted_steps(cfg_name: str, seq: int, batch: int, max_len: int):
    """Returns (prefill, decode, arena_decode), all jitted.

    ``arena_decode(params, caches, tokens, pos, mask)`` is the masked
    batched step of the dense slot arena (DESIGN.md §9, §15): ``tokens``
    (B, w), ``pos`` (B,) per-slot next cache positions, ``mask`` (B,)
    live-slot flags.  Every slot feeds its ``w`` tokens at positions
    ``pos..pos+w-1`` in ONE model call and gets the ``w`` greedy argmax
    outputs back, (B, w): plain decode is ``w = 1``, a speculative
    verify ``w = 1 + drafts``; the width is the token block's static
    shape, so jit compiles once per width.  Parked rows (mask False —
    free slots and this iteration's fresh prefills) are pinned to the
    scratch rows from ``max_len - w``, which no live query position ever
    attends to, so their cache writes are inert, and an SSM layer's
    state of a parked row is kept bit for bit (``where(mask, new, old)``).
    Rows a live slot writes past its committed position (rejected
    drafts) are never read: reads stop at each slot's position, and
    later steps overwrite them.  The caller pulls the (B, w) tokens back
    once per iteration.
    """
    from repro.models import decode_step, prefill

    cfg = get_config(cfg_name)
    pre = jax.jit(lambda p, b: prefill(cfg, p, b, max_len=max_len))
    dec = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))

    def _arena(p, c, t, pos, mask):
        pos = jnp.where(mask, pos, max_len - t.shape[1]).astype(jnp.int32)
        logits, new = decode_step(cfg, p, c, t, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (jnp.where(mask[:, None], nxt, 0),
                _hold_parked_state(cfg, new, c, mask))

    return pre, dec, jax.jit(_arena)


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
