"""Smoke run of the serving main path on a TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --chips 4     # four chips: the placement phase only
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny    # CPU rehearsal, toy sizes
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python chip_smoke.py --tiny --chips 4

One process drives every phase and prints one JSON line per phase.  The
last line is ``{"ok": true, "device": {...}}``; a failed check or an
exception exits non-zero before it.  One chip runs:

1. ``device``: JAX must see TPU devices (``--tiny`` alone admits the CPU).
2. ``kernels``: every Pallas op compiled as a Mosaic kernel
   (``interpret=False``) in int8 and int4 at serving widths, against its
   ``*_ref`` oracle at the tolerances of ``tests/test_kernels.py``.
3. ``byte_lm``: offline profiles -> ``ServiceAwareController`` -> a 1x1
   ``ClusterRuntime`` on the measured clock, in PD mode (dense arena) and
   pool mode (paged arena).
4. ``qwen3-4b``: the same PD serving at the registered widths with bf16
   weights drawn on the device from ``--seed``.

Four chips run ``placement`` instead: a 2x2 PD cluster with one worker per
chip against the same requests on a 1x1 cluster; greedy tokens must match.

Times (TTFT, JCT) are printed as information; nothing here is a benchmark.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORKLOADS = ("qalike", "codelike", "mathlike", "summlike")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# 1. Device
# ---------------------------------------------------------------------------
def device_check(args, cache_dir: str):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.tiny:
        fail(f"JAX found no TPU (platform {platform!r}); this script has no "
             f"CPU fallback (--tiny rehearses on the CPU)")
    check(len(devices) >= args.chips,
          f"{args.chips} devices needed, JAX sees {len(devices)}")
    emit("device", platform=platform, kind=devices[0].device_kind,
         count=len(devices), compile_cache=cache_dir)
    return devices


# ---------------------------------------------------------------------------
# 2. Kernels
# ---------------------------------------------------------------------------
def _paged_pools(K, k, v, bits, group, page, rng):
    """Scatter dense (B, H, S, D) K/V into shuffled quantized page pools
    (page 0 is the unmapped scratch page)."""
    b, h, s, d = k.shape
    kc8, ks = K.quantize_ref(k, bits, group)
    vc8, vs = K.quantize_ref(v, bits, group)
    kc = K.pack_int4_ref(kc8) if bits == 4 else kc8
    vc = K.pack_int4_ref(vc8) if bits == 4 else vc8
    pps = s // page
    bt = rng.permutation(np.arange(1, 1 + b * pps)).reshape(b, pps)

    def scatter(x):
        x = np.asarray(x)                       # (B, H, S, X)
        pages = x.reshape(b, h, pps, page, -1).transpose(0, 2, 1, 3, 4)
        pool = np.zeros((1 + b * pps, h, page, x.shape[-1]), x.dtype)
        pool[bt.reshape(-1)] = pages.reshape(b * pps, h, page, -1)
        return jnp.asarray(pool)

    pools = tuple(scatter(x) for x in (kc, ks, vc, vs))
    return pools, jnp.asarray(bt, jnp.int32)


def phase_kernels(args):
    from repro.kernels import ops as K

    d, hkv, gq, page, group = 128, 8, 4, 16, 64     # serving widths
    t, b, s, w = (256, 2, 64, 3) if args.tiny else (2048, 4, 512, 4)
    rng = np.random.default_rng(args.seed)

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def exact(fn, *a, **kw):
        # The oracles at full f32 matmul precision (TPU's XLA default
        # multiplies f32 in one bf16 pass).
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)

    def err(got, want):
        return float(np.max(np.abs(np.asarray(got, np.float64)
                                    - np.asarray(want, np.float64))))

    def close(got, want, rtol, atol):
        return bool(np.allclose(np.asarray(got, np.float64),
                                np.asarray(want, np.float64),
                                rtol=rtol, atol=atol))

    results, bad = {}, []

    def record(name, ok, max_err):
        results[name] = max_err
        if not ok:
            bad.append(name)

    # Mosaic on the chip; the Pallas interpreter only in a CPU rehearsal
    off = dict(group=group, interpret=jax.default_backend() != "tpu")
    for bits in (8, 4):
        # quant_pack: bf16 KV in; codes may move by one at rounding ties
        x = normal(t, d, scale=4.0, dtype=jnp.bfloat16)
        codes, scales = K.quant_pack_op(x, bits=bits, **off)
        cref, sref = exact(K.quant_pack_ref, x.astype(jnp.float32), bits,
                           group)
        got, want = codes, cref
        if bits == 4:
            got, want = K.unpack_int4_ref(codes), K.unpack_int4_ref(cref)
        diff = np.asarray(got, np.int32) - np.asarray(want, np.int32)
        ok = (np.abs(diff).max() <= 1
              and (diff != 0).mean() < (1e-2 if bits == 4 else 1e-3)
              and close(scales, sref, 1e-5, 1e-7))
        record(f"quant_pack_int{bits}", ok, err(got, want))

        # dequant_unpack
        xf = normal(t, d, scale=3.0)
        codes, scales = K.quant_pack_ref(xf, bits, group)
        got = K.dequant_unpack_op(codes, scales, bits=bits,
                                  out_dtype=jnp.float32, **off)
        want = exact(K.dequant_unpack_ref, codes, scales, bits, group,
                     dtype=jnp.float32)
        record(f"dequant_unpack_int{bits}", close(got, want, 1e-6, 1e-6),
               err(got, want))

        # decode attention over per-slot ragged lengths
        q = normal(b, hkv, gq, d)
        k, v = normal(b, hkv, s, d), normal(b, hkv, s, d)
        kc8, ks = K.quantize_ref(k, bits, group)
        vc8, vs = K.quantize_ref(v, bits, group)
        kc = K.pack_int4_ref(kc8) if bits == 4 else kc8
        vc = K.pack_int4_ref(vc8) if bits == 4 else vc8
        lens = jnp.asarray([s, s // 2, 3, s - 17][:b], jnp.int32)
        got = K.decode_attention_op(q, kc, ks, vc, vs, bits=bits,
                                    kv_len=lens, block_s=min(256, s), **off)
        want = exact(K.decode_attention_ref, q, kc8, ks, vc8, vs, group,
                     kv_len=lens)
        record(f"decode_attention_int{bits}", close(got, want, 1e-4, 2e-5),
               err(got, want))

        # paged decode and paged multi-token verify attention
        pools, bt = _paged_pools(K, k, v, bits, group, page, rng)
        lens = jnp.asarray([s, s // 2 - 3, 1, s - 5][:b], jnp.int32)
        got = K.paged_attention_op(q, *pools, bt, lens, bits=bits, **off)
        want = exact(K.paged_attention_ref, q, *pools, bt, lens, bits=bits,
                     group=group)
        record(f"paged_attention_int{bits}", close(got, want, 1e-4, 2e-5),
               err(got, want))
        qw = normal(b, hkv, w, gq, d)
        lens = jnp.asarray([s - w, s // 2 - 3, 1, s - 9][:b], jnp.int32)
        got = K.paged_verify_attention_op(qw, *pools, bt, lens, bits=bits,
                                          **off)
        want = exact(K.paged_verify_attention_ref, qw, *pools, bt, lens,
                     bits=bits, group=group)
        record(f"paged_verify_attention_int{bits}",
               close(got, want, 1e-4, 2e-5), err(got, want))

    x = normal(t, d)
    got = K.hadamard_op(x, interpret=off["interpret"])
    want = exact(K.hadamard_ref, x)
    record("hadamard", close(got, want, 0.0, 1e-5), err(got, want))

    emit("kernels", max_abs_err=results, failed=bad)
    check(not bad, f"kernels disagree with their oracles: {bad}")


# ---------------------------------------------------------------------------
# Serving helpers
# ---------------------------------------------------------------------------
def runtime_config(args, **kw):
    from repro.serving.workers import RuntimeConfig
    # seq + decode_tokens + 2 is a multiple of page_size (paged parity)
    seq, dec, page = (32, 6, 8) if args.tiny else (96, 14, 16)
    return RuntimeConfig(seq=seq, decode_tokens=dec, page_size=page, **kw)


def scheduler_config():
    from repro.serving.scheduler import SchedulerConfig
    return SchedulerConfig(max_slots=4, max_prefills_per_step=2, max_queue=64)


def serve(rt, waves, q_min: float = 0.3):
    """Submit each wave once the previous one has drained (so repeated
    prompts find the prefix their first copy stored)."""
    submitted = {}
    for wave in waves:
        for workload, seed in wave:
            rid = rt.submit(workload, q_min=q_min, prompt_seed=seed)
            check(rid is not None, f"request {workload}/{seed} was shed")
            submitted[rid] = (workload, seed)
        rt.run()
    return submitted


def check_served(rt, submitted, label: str) -> None:
    done = {r.rid: r for r in rt.completed}
    check(set(done) == set(submitted),
          f"{label}: {len(done)} of {len(submitted)} requests completed")
    budget = rt.cfg.decode_tokens + 1              # first token + decode
    vocab = rt.model_cfg.vocab_size
    for r in done.values():
        check(len(r.tokens) == budget,
              f"{label}: request {r.rid} has {len(r.tokens)} tokens, "
              f"expected {budget}")
        check(all(0 <= int(t) < vocab for t in r.tokens),
              f"{label}: request {r.rid} emitted a token outside the vocab")
        total = sum(r.breakdown.values())
        check(abs(total - r.jct) <= 1e-9 * max(1.0, r.jct),
              f"{label}: request {r.rid} breakdown {total} != jct {r.jct}")


def check_reference(rt, submitted, label: str) -> int:
    """Teacher-forced agreement with a plain batch-1 greedy decode of the
    same prompt: at every step of every cold request (its arena row holds
    the exact prefill cache) the runtime's token must be the reference's
    argmax up to bf16 resolution.  Returns the number of tokens checked."""
    from repro.core.quality import _jitted_steps, _prompts_for

    cfg, params, rc = rt.model_cfg, rt.params, rt.cfg
    pre, dec, _ = _jitted_steps(cfg.name, rc.seq, 1, rc.arena_max_len)
    n = 0
    for r in rt.completed:
        if r.pool_hit:
            continue
        workload, seed = submitted[r.rid]
        prompt, _ = _prompts_for(workload, 1, rc.seq, seed)
        logits, caches = pre(params, {"tokens": prompt})
        for t, tok in enumerate(r.tokens):
            if t > 0:
                logits, caches = dec(params, caches,
                                     jnp.asarray([[r.tokens[t - 1]]],
                                                 jnp.int32),
                                     jnp.asarray(rc.seq + t - 1, jnp.int32))
            row = np.asarray(logits[0, -1], np.float32)
            check(bool(np.isfinite(row).all()),
                  f"{label}: non-finite reference logits")
            top = float(row.max())
            check(row[int(tok)] >= top - 2 ** -6 * max(1.0, abs(top)),
                  f"{label}: request {r.rid} token {t} = {int(tok)} has "
                  f"logit {row[int(tok)]} against the reference max {top}")
            n += 1
    return n


def timing_info(rt) -> dict:
    s = rt.summary()
    return {k: s[k] for k in ("mean_ttft", "mean_jct") if k in s}


# ---------------------------------------------------------------------------
# 3. Byte LM through the controller
# ---------------------------------------------------------------------------
def build_controller(args):
    from repro.controller import ServiceAwareController
    from repro.core.strategy import BASELINES, StrategyConfig
    from repro.launch.profile_offline import build_profiles

    strategies = [BASELINES["kivi"], BASELINES["cachegen"], BASELINES["mixhq"],
                  StrategyConfig(quantizer="uniform", key_bits=8,
                                 value_bits=8, granularity="per_channel"),
                  StrategyConfig(quantizer="uniform", key_bits=4,
                                 value_bits=4, granularity="per_channel",
                                 codec="zstd3")]
    qk = ({"n_prompts": 2, "decode_tokens": 4, "seq": 64} if args.tiny
          else {"n_prompts": 4, "decode_tokens": 12})
    profiles = build_profiles(strategies, quality_kwargs=qk)
    return ServiceAwareController({w: profiles for w in WORKLOADS})


BYTE_LM_WAVES = ([("qalike", 0), ("codelike", 1), ("mathlike", 2),
                  ("summlike", 3)],
                 [("qalike", 0), ("codelike", 1), ("mathlike", 4),
                  ("summlike", 5)])


def phase_byte_lm(args, controller):
    from repro.serving.cluster import ClusterRuntime

    for mode, paged in (("pd", False), ("pool", True)):
        for _ in range(2):      # the first run compiles every shape
            rt = ClusterRuntime(
                controller=controller, scheduler=scheduler_config(),
                config=runtime_config(args, mode=mode, paged=paged))
            submitted = serve(rt, BYTE_LM_WAVES)
        label = f"byte_lm/{mode}"
        check_served(rt, submitted, label)
        s = rt.summary()
        if mode == "pd":
            check(s["wire_bytes_moved"] > 0, f"{label}: no wire bytes")
        else:
            check(s["pool_hits"] >= 1, f"{label}: no pool hit")
        checked = check_reference(rt, submitted, label)
        emit(f"byte_lm_{mode}", paged=paged, requests=len(submitted),
             pool_hits=s["pool_hits"], wire_bytes=s["wire_bytes_moved"],
             reference_tokens_checked=checked, **timing_info(rt))


# ---------------------------------------------------------------------------
# 4. Published width
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, ones):
    if ones:
        return jnp.ones(shape, jnp.bfloat16)
    return jax.random.normal(key, shape, jnp.bfloat16) * 0.02


def device_params(cfg, seed: int):
    """bf16 weights of ``cfg``'s shapes drawn on the default device: norm
    scales one, every other leaf N(0, 0.02).  (A host-side init of 4.4 B
    parameters would take minutes and stage through host memory.)"""
    from repro.models import init_params

    shapes, _ = init_params(cfg, abstract=True, dtype=jnp.bfloat16)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(seed), len(flat))
    leaves = [_draw(k, tuple(s.shape),
                    jax.tree_util.keystr(p).endswith(("['scale']", "_norm']")))
              for (p, s), k in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def phase_published(args, controller):
    from repro.configs import get_config
    from repro.serving.cluster import ClusterRuntime

    name = "qwen3-4b-reduced" if args.tiny else "qwen3-4b"
    cfg = get_config(name)
    t0 = time.perf_counter()
    params = device_params(cfg, args.seed)
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    waves = ([("qalike", 0), ("codelike", 1)],
             [("qalike", 0), ("mathlike", 2)])
    for _ in range(2):          # the first run compiles every shape
        rt = ClusterRuntime(controller=controller,
                            scheduler=scheduler_config(),
                            config=runtime_config(args, mode="pd"))
        rt.model_cfg, rt.params = cfg, params
        submitted = serve(rt, waves)
    label = name
    check_served(rt, submitted, label)
    s = rt.summary()
    check(s["wire_bytes_moved"] > 0, f"{label}: no wire bytes")
    checked = check_reference(rt, submitted, label)
    stats = jax.devices()[0].memory_stats() or {}
    emit(name, params=n_params, dtype="bfloat16", init_s=t_init,
         requests=len(submitted), pool_hits=s["pool_hits"],
         wire_bytes=s["wire_bytes_moved"], reference_tokens_checked=checked,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"), **timing_info(rt))


# ---------------------------------------------------------------------------
# Four chips: one worker per chip
# ---------------------------------------------------------------------------
def placed_on(rt, prefill_devices, decode_devices) -> bool:
    """Every worker's params, arena and page pools on its own device."""
    def on(tree, dev):
        return all(x.devices() == {dev}
                   for x in jax.tree_util.tree_leaves(tree))

    return (all(on(w.params, d)
                for w, d in zip(rt.prefill_workers, prefill_devices))
            and all(on((w.params,) + w.layout.arrays, d)
                    for w, d in zip(rt.decode_workers, decode_devices)))


def phase_placement(args, devices):
    from repro.core.profiles import Profile
    from repro.core.strategy import StrategyConfig
    from repro.serving.cluster import ClusterRuntime

    profile = Profile(StrategyConfig(quantizer="uniform", key_bits=8,
                                     value_bits=8, granularity="per_channel"),
                      cr=2.0, s_enc=5e8, s_dec=5e8)
    # distinct prompts: every request takes the cold path across its route
    requests = [(WORKLOADS[i % 4], 100 + i) for i in range(8)]
    pdev, ddev = list(devices[:2]), list(devices[2:4])
    for variant in ({"paged": False, "pd_inject_restored": False},
                    {"paged": True, "pd_inject_restored": True}):
        tokens = {}
        for n, pd_, dd_ in ((1, None, None), (2, pdev, ddev)):
            rt = ClusterRuntime(static_profile=profile,
                                scheduler=scheduler_config(),
                                config=runtime_config(args, mode="pd",
                                                      **variant),
                                n_prefill=n, n_decode=n,
                                router="round_robin",
                                prefill_devices=pd_, decode_devices=dd_)
            submitted = serve(rt, [requests])
            label = f"placement/{n}x{n}"
            check_served(rt, submitted, label)
            check(rt.summary()["wire_bytes_moved"] > 0,
                  f"{label}: no wire bytes")
            if pd_ is not None:
                check(placed_on(rt, pd_, dd_),
                      f"{label}: a worker's arrays left its device")
                check(len({r.route for r in rt.completed}) == 4,
                      f"{label}: not every route served")
            tokens[n] = {r.rid: np.asarray(r.tokens).tolist()
                         for r in rt.completed}
        differ = sorted(rid for rid in tokens[1]
                        if tokens[1][rid] != tokens[2].get(rid))
        emit("placement", **variant, requests=len(requests),
             devices=[str(d) for d in pdev + ddev], token_mismatches=differ)
        check(not differ, f"2x2 tokens differ from 1x1 for {differ}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the one-worker-per-chip phase")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes; admits the CPU (rehearsal only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"no src/repro next to {Path(__file__).name}: run it from a "
             f"checkout of the repository")
    # Program caches (the trained byte LM) stay inside the checkout.
    os.environ.setdefault("REPRO_CACHE_DIR", str(ROOT / ".repro_cache"))
    if args.tiny:
        os.environ.setdefault("REPRO_REF_STEPS", "30")
    sys.path.insert(0, str(SRC))
    from repro.utils.compile_cache import enable_compile_cache

    devices = device_check(args, enable_compile_cache())
    if args.chips == 4:
        phase_placement(args, devices)
    else:
        phase_kernels(args)
        controller = build_controller(args)
        phase_byte_lm(args, controller)
        phase_published(args, controller)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
