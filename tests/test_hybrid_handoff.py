"""A hybrid model (Jamba2-3B's rehearsal size) through the PD hand-off: the
prefill, the compressed hand-off of the attention layers' KV together with
every SSM layer's recurrent state, and the masked arena decode agree with
the plain float32 reference of ``bench/archs/jamba2.py``; the state is
part of the payload, of its byte counts and of the size the controller
prices.

Weights: every leaf N(0, 0.02) in bf16, as the benchmark draws them
(``bench/weights.py``), except each mixer's ``a_log`` and ``dt_proj_b``,
which keep the program's own long-memory init (A = -[1..16], dt near
0.01): the drawn values make the state halve with every token, and the
state's part in the logits would fade within a few tokens.  The
program's whole own init is not used: at its scales 14 layers of bf16
activations drift from the float32 reference by tens of logits.
"""
import importlib.util
import re
from dataclasses import asdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.kvcache import KVCache, handoff_bytes
from repro.core.profiles import IDENTITY_PROFILE
from repro.core.strategy import StrategyConfig

BENCH = Path(__file__).resolve().parents[1] / "bench"
PROMPT, OUT = 64, 8
MAX_LEN = PROMPT + OUT + 2
SEED = 2**31 + 11
# Max |program - reference| over the logits of every served position.  The
# program runs bf16 activations through 14 layers against the reference's
# float32: sound hand-offs read 0.042 (identity) and 0.048 (4-bit KV, which
# the reference quantizes alike); a zeroed or stale state reads 0.22-0.67
# at its worst decoded position, most at the first ones.
LOGIT_TOL = 0.1
UNIFORM4 = StrategyConfig(quantizer="uniform", key_bits=4, value_bits=4,
                          granularity="per_channel", group_size=32)


def _bench(name: str):
    import sys

    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    path = BENCH / "archs" / f"{name}.py" if name == "jamba2" else \
        BENCH / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    return _bench("jamba2")


@pytest.fixture(scope="module")
def conf():
    import json

    return json.loads((BENCH / "configs" / "jamba2-3b.json").read_text())


@pytest.fixture(scope="module")
def model():
    """The rehearsal size, its bench-drawn weights with the program's
    long-memory A and dt bias."""
    from repro.models import init_params

    cfg = get_config("jamba2-3b-reduced")
    params = _bench("weights").draw_params(cfg, SEED)
    own, _ = init_params(cfg, seed=0)
    for name, layer in params["blocks"].items():
        for key in ("a_log", "dt_proj_b"):
            if key in layer["mixer"]:
                layer["mixer"][key] = own["blocks"][name]["mixer"][
                    key].astype(jnp.bfloat16)
    return cfg, params


def _leaf(params):
    """The reference's weights from the program's tree, by path."""
    def leaf(path, shape, layer):
        node = params
        for key in re.findall(r"\['([^']+)'\]", path):
            node = node[key]
        a = node if layer < 0 else node[layer]
        assert tuple(a.shape) == tuple(shape), (path, a.shape, shape)
        return jnp.asarray(a, jnp.float32)
    return leaf


def _prompts(n: int) -> np.ndarray:
    return np.random.default_rng(5).integers(0, 259, (n, PROMPT)).astype(
        np.int32)


def _decode_worker(cfg, params, n_slots: int):
    from repro.serving.workers import DecodeWorker, ModelHandle, RuntimeConfig

    rc = RuntimeConfig(seq=PROMPT, decode_tokens=OUT, mode="pd")
    assert rc.arena_max_len == MAX_LEN
    return DecodeWorker(0, ModelHandle(cfg, params), rc, n_slots, store=None)


def _hand_off(cfg, params, prompt, strategy):
    """Prefill one prompt; its hand-off through ``strategy`` as the decode
    side restores it, and the prefill's logits."""
    from repro.core.quality import DeviceKV, _jitted_steps
    from repro.serving.workers import compress_kvs, decompress_kvs

    pre, _, _ = _jitted_steps(cfg.name, PROMPT, 1, MAX_LEN)
    logits, caches = pre(params, {"tokens": jnp.asarray(prompt[None])})
    comps, wire, _ = compress_kvs(strategy, [DeviceKV(cfg, caches, 0,
                                                      PROMPT)])
    (kv,), _ = decompress_kvs(comps)
    return kv, np.asarray(logits[0, -1], np.float32), wire


def _serve(cfg, params, prompts, strategy, fault=None):
    """Prompts 0 and 2 of three go through the hand-off into arena slots 0
    and 2, then decode in the masked arena step with slot 1 parked.
    Returns each served request's logits (T, V) and tokens."""
    from repro.core.quality import _jitted_steps

    dw = _decode_worker(cfg, params, 3)
    live = [0, 2]
    first, rows = {}, {}
    stale, _, _ = _hand_off(cfg, params, prompts[1], strategy)
    for slot in live:
        kv, pre_logits, _ = _hand_off(cfg, params, prompts[slot], strategy)
        if fault == "state_zeroed":
            kv = KVCache(kv.k, kv.v, {n: np.zeros_like(a)
                                      for n, a in kv.state.items()})
        elif fault == "state_not_injected":
            # the slot holds an earlier request's state; this one's KV
            # lands without its own
            dw.inject_restored(stale, slot)
            kv = KVCache(kv.k, kv.v)
        dw.inject_restored(kv, slot)
        first[slot] = int(np.argmax(pre_logits))
        rows[slot] = [pre_logits]
    _, dec, _ = _jitted_steps(cfg.name, PROMPT, 3, MAX_LEN)
    arena, layout = dw.layout.program(), dw.layout
    mask = jnp.asarray([s in live for s in range(3)])
    toks = np.zeros((3, 1), np.int32)
    pos = np.full(3, PROMPT, np.int32)
    served = {s: [first[s]] for s in live}
    for s in live:
        toks[s, 0] = first[s]
    for _ in range(OUT - 1):
        # the logits the arena step takes its argmax of: the same model
        # step over the same arena, before the step replaces it
        logits, _ = dec(params, layout.cache, jnp.asarray(toks),
                        jnp.where(mask, jnp.asarray(pos), MAX_LEN - 1))
        nxt, layout.cache = arena(params, layout.cache, jnp.asarray(toks),
                                  jnp.asarray(pos), mask)
        nxt = np.asarray(nxt)[:, 0]
        for s in live:
            rows[s].append(np.asarray(logits[s, -1], np.float32))
            served[s].append(int(nxt[s]))
            toks[s, 0] = nxt[s]
            pos[s] += 1
    return ({s: np.stack(rows[s]) for s in live},
            {s: np.asarray(served[s], np.int32) for s in live})


def _reference_logits(arch, conf, params, prompts, served, strategy):
    from reference import Served

    ref = arch.Reference(conf["rehearsal"], conf["architecture"], SEED,
                         leaf=_leaf(params))
    st = None if strategy is IDENTITY_PROFILE.strategy else asdict(strategy)
    return np.asarray(ref.logits([Served(prompt=prompts[s],
                                         tokens=served[s], strategy=st)
                                  for s in sorted(served)]))


@pytest.mark.parametrize("handoff", ["identity", "uniform4", "state_zeroed",
                                     "state_not_injected"])
def test_prefill_handoff_and_arena_decode_match_the_reference(
        arch, conf, model, handoff):
    """Sound hand-offs keep every served position's logits within
    ``LOGIT_TOL`` of the reference; a hand-off that loses the state
    leaves the prefill's logits alone and fails every decoded position."""
    cfg, params = model
    strategy = UNIFORM4 if handoff == "uniform4" else \
        IDENTITY_PROFILE.strategy
    fault = handoff if handoff.startswith("state_") else None
    prompts = _prompts(3)
    got, served = _serve(cfg, params, prompts, strategy, fault)
    want = _reference_logits(arch, conf, params, prompts, served, strategy)
    diff = np.stack([np.abs(got[s] - want[b]).max(-1)
                     for b, s in enumerate(sorted(got))])   # (B, T)
    assert diff[:, 0].max() <= LOGIT_TOL, diff[:, 0]         # the prefill
    if fault is None:
        assert diff.max() <= LOGIT_TOL, diff
        # and each token the arena served is within the tolerance of the
        # reference's best there (twice: the two sides' roundings add)
        mine = np.stack([np.take_along_axis(
            want[b], served[s][:, None], -1)[:, 0]
            for b, s in enumerate(sorted(served))])
        assert (want.max(-1) - mine).max() <= 2 * LOGIT_TOL
    else:
        # the state's loss shows most in the first decoded tokens and
        # fades as the decode rebuilds it: every request fails, by twice
        # the tolerance at least
        assert (diff[:, 1:].max(1) > 2 * LOGIT_TOL).all(), diff


def test_a_parked_slot_keeps_its_state_bit_for_bit(model):
    """The masked arena step leaves a parked slot's SSM and conv state as
    it was; the unmasked model step would have advanced it."""
    from repro.models import decode_step

    cfg, params = model
    prompts = _prompts(2)
    dw = _decode_worker(cfg, params, 2)
    for slot in (0, 1):
        kv, _, _ = _hand_off(cfg, params, prompts[slot],
                             IDENTITY_PROFILE.strategy)
        dw.inject_restored(kv, slot)
    toks = jnp.asarray([[3], [4]], jnp.int32)
    pos = jnp.full((2,), PROMPT, jnp.int32)
    mask = jnp.asarray([True, False])
    before = dw.layout.cache
    _, after = dw.layout.program()(params, before, toks, pos, mask)
    _, unmasked = decode_step(cfg, params, before, toks, pos)
    n_state = 0
    for name, layer in before["blocks"].items():
        if "ssm" not in layer:
            continue
        for t in ("ssm", "conv"):
            old, new = np.asarray(layer[t]), np.asarray(
                after["blocks"][name][t])
            assert new.dtype == old.dtype
            np.testing.assert_array_equal(new[:, 1], old[:, 1])
            assert not np.array_equal(new[:, 0], old[:, 0])
            assert not np.array_equal(
                np.asarray(unmasked["blocks"][name][t])[:, 1], old[:, 1])
            n_state += 1
    assert n_state == 2 * 13


def test_the_handoff_carries_the_state_and_counts_it(arch, conf, model):
    """The payload that crosses is the module's ``handoff_bytes``: the two
    attention layers' KV and all the state; the state crosses exactly."""
    cfg, params = model
    prompt = _prompts(1)[0]
    kv, _, wire = _hand_off(cfg, params, prompt, IDENTITY_PROFILE.strategy)
    m = {**conf["rehearsal"], **conf["architecture"]}
    assert kv.k.shape == (1, 1, PROMPT, 128)
    assert kv.state["ssm"].shape == (13, 1024, 16)
    assert kv.state["conv"].shape == (13, 1024, 3)
    assert kv.state["ssm"].dtype == np.float32
    assert kv.state["conv"].dtype == jnp.bfloat16
    assert kv.nbytes_wire() == arch.handoff_bytes(m, PROMPT) \
        == handoff_bytes(cfg, PROMPT)
    assert wire == kv.nbytes_wire() + 64          # the message header
    # the published size: 2 x 2048 x 128 x 2 B of KV and 26 layers of
    # 5120 x (16 x 4 B + 3 x 2 B) of state
    full = {**conf["used"], **conf["architecture"]}
    assert arch.state_bytes(full) == 9318400
    assert arch.handoff_bytes(full, 2048) == 2097152 + 9318400 \
        == handoff_bytes(get_config("jamba2-3b"), 2048)

    from repro.core.quality import _jitted_steps
    from repro.models.transformer import plan_stack

    pre, _, _ = _jitted_steps(cfg.name, PROMPT, 1, MAX_LEN)
    _, caches = pre(params, {"tokens": jnp.asarray(prompt[None])})
    plan = plan_stack(cfg)
    ssm = [j for j, s in enumerate(plan.period_specs) if s.kind == "ssm"]
    for i, j in enumerate(ssm):
        for t in ("ssm", "conv"):
            np.testing.assert_array_equal(
                kv.state[t][i], np.asarray(caches["blocks"][f"layer{j}"][t]
                                           [0, 0]))


@pytest.mark.parametrize("name", ["jamba2-3b", "jamba2-3b-reduced"])
def test_param_count_agrees_with_the_module(arch, conf, name):
    from repro.models import init_params

    cfg = get_config(name)
    shapes, _ = init_params(cfg, abstract=True)
    program = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    block = conf["used" if name == "jamba2-3b" else "rehearsal"]
    assert program == arch.param_count({**block, **conf["architecture"]})
    if name == "jamba2-3b":
        assert program == 3029337472
        assert arch.check(cfg, conf["used"], conf["architecture"]) == {}
    else:
        assert arch.check(cfg, conf["rehearsal"],
                          conf["architecture"]) == {}


@pytest.mark.parametrize("name", ["qwen3-4b-reduced", "jamba2-3b-reduced"])
def test_the_controller_prices_the_payload_it_sends(name, tmp_path,
                                                    monkeypatch):
    """The size a request is admitted and routed with is the size the
    hand-off's ``compress`` span counts (the attention layers' KV plus any
    state), and the state on the wire is the state the pull took."""
    import repro.serving.cluster as cluster
    from repro.models import init_params
    from repro.serving import tracing
    from repro.serving.scheduler import SchedulerConfig
    from repro.serving.workers import RuntimeConfig

    cfg = get_config(name)
    params, _ = init_params(cfg, seed=0)
    monkeypatch.setattr(cluster, "get_reference_model",
                        lambda: (cfg, params))
    rt = cluster.ClusterRuntime(
        static_profile=IDENTITY_PROFILE,
        config=RuntimeConfig(seq=32, decode_tokens=2, mode="pd",
                             pd_inject_restored=True),
        scheduler=SchedulerConfig(max_slots=2))
    tracing.SPANS.clear()
    with jax.profiler.trace(str(tmp_path)):
        rid = rt.submit("qalike", prompt_seed=3)
        rt.run()
    spans = list(tracing.SPANS)
    tracing.SPANS.clear()
    (done,) = rt.completed
    (comp,) = [s for s in spans if s.name == "compress"]
    (pull,) = [s for s in spans if s.name == "kv_pull"]
    (inject,) = [s for s in spans if s.name == "inject"]
    assert done.rid == rid
    assert done.kv_bytes == comp.counters["kv_bytes"] \
        == handoff_bytes(cfg, 32)
    assert comp.counters["state_bytes"] == pull.counters["state_bytes"] \
        == inject.counters["state_bytes"]
    states = [s for s in spans if s.name == "state_inject"]
    if name.startswith("qwen"):
        assert comp.counters["state_bytes"] == 0 and not states
        assert pull.counters["transfers"] == 1
    else:
        assert comp.counters["state_bytes"] == 13 * 1024 * (16 * 4 + 3 * 2)
        assert [s.parent for s in states] == [inject]


@pytest.mark.parametrize("arena", [{"paged": True}, {"spec_k": 2}])
def test_arenas_without_per_slot_state_refuse_ssm_layers(model, arena):
    """Only the dense one-token step keeps a parked slot's state; the paged
    pools and the multi-token verify step would advance or drop it."""
    from repro.serving.workers import DecodeWorker, ModelHandle, RuntimeConfig

    cfg, params = model
    dw = DecodeWorker(0, ModelHandle(cfg, params),
                      RuntimeConfig(seq=PROMPT, mode="pd", **arena), 2,
                      store=None)
    with pytest.raises(NotImplementedError, match="SSM layers"):
        dw.ensure_arena()
