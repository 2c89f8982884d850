"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
output shapes + no NaNs; prefill/decode consistency vs full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.configs.base import reduce_config, supported_shapes
from repro.distribution.optimizer import OptConfig, init_opt_state
from repro.distribution.steps import make_train_step
from repro.models import decode_step, forward, init_params, make_inputs, prefill


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_and_train_step(arch):
    cfg = reduce_config(get_config(arch))
    params, axes = init_params(cfg, seed=0)
    inp = make_inputs(cfg, "train", seq=32, batch=2, abstract=False, seed=1)

    # forward (shifted inputs)
    b = dict(inp["batch"])
    b["tokens"] = b["tokens"][:, :-1]
    logits, aux = forward(cfg, params, b)
    exp_len = b["tokens"].shape[1] if cfg.family != "vlm" else 32
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab_size
    assert bool(jnp.isfinite(logits).all()), f"{arch}: NaN/inf logits"

    # one real optimizer step
    oc = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt_state = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, oc, remat=False))
    params2, opt_state2, metrics = step(params, opt_state, inp["batch"])
    assert bool(jnp.isfinite(metrics["loss"])), f"{arch}: NaN loss"
    assert float(metrics["loss"]) > 0
    # params actually changed
    l0 = jax.tree_util.tree_leaves(params)[1]
    l1 = jax.tree_util.tree_leaves(params2)[1]
    assert not np.allclose(np.asarray(l0), np.asarray(l1))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_available(arch):
    cfg = reduce_config(get_config(arch))
    params, _ = init_params(cfg, seed=0)
    pin = make_inputs(cfg, "prefill", seq=24, batch=2, abstract=False, seed=2)
    logits, caches = prefill(cfg, params, pin["batch"],
                             max_len=pin["max_len"] + 4)
    assert logits.shape == (2, 1, cfg.vocab_size)
    pos = jnp.asarray(pin["batch"]["tokens"].shape[1], jnp.int32)
    dlog, caches2 = decode_step(cfg, params, caches,
                                jnp.zeros((2, 1), jnp.int32), pos)
    assert dlog.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(dlog).all())


@pytest.mark.parametrize("arch", ["qwen3-4b", "gemma2-9b", "falcon-mamba-7b",
                                  "deepseek-moe-16b", "jamba-v0.1-52b"])
def test_decode_consistency_with_forward(arch):
    """Teacher-forced decode must reproduce the full-forward logits.

    MoE capacity dropping is sequence-length dependent (tokens compete for
    expert slots), so the consistency check runs with a no-drop capacity
    factor — the dropped-token divergence is expected MoE semantics, not a
    cache bug."""
    from dataclasses import replace
    cfg = reduce_config(get_config(arch))
    if cfg.moe:
        cfg = replace(cfg, capacity_factor=16.0)
    params, _ = init_params(cfg, seed=0)
    rng = np.random.default_rng(0)
    T = 12
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, T + 1)),
                         jnp.int32)

    full_logits, _ = forward(cfg, params, {"tokens": tokens})
    _, caches = prefill(cfg, params, {"tokens": tokens[:, :T]}, max_len=T + 1)
    dlog, _ = decode_step(cfg, params, caches, tokens[:, T:T + 1],
                          jnp.asarray(T, jnp.int32))
    a = np.asarray(full_logits[:, T, :], np.float32)
    b = np.asarray(dlog[:, 0, :], np.float32)
    # identical math, bf16 accumulation differences only
    assert np.argmax(a, -1).tolist() == np.argmax(b, -1).tolist()
    np.testing.assert_allclose(a, b, atol=0.15, rtol=0.05)


def test_param_counts_sane():
    """Full-config param counts in the right ballpark (catches config typos)."""
    expect = {
        "qwen3-4b": (3e9, 7e9),
        "gemma2-9b": (8e9, 13e9),
        # note: assigned config prescribes llama-arch (gated GLU) at
        # d_ff=24576, which lands above the namesake's 20B
        "granite-20b": (15e9, 30e9),
        "minicpm-2b": (2e9, 3.5e9),
        "jamba-v0.1-52b": (40e9, 65e9),
        "whisper-small": (0.15e9, 0.45e9),
        "qwen2-vl-72b": (60e9, 85e9),
        "llama4-scout-17b-a16e": (85e9, 120e9),  # total (17B active)
        "deepseek-moe-16b": (14e9, 20e9),
        "falcon-mamba-7b": (6e9, 9e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params not in [{lo/1e9}, {hi/1e9}]"


def test_active_params_less_than_total_for_moe():
    for arch in ("llama4-scout-17b-a16e", "deepseek-moe-16b",
                 "jamba-v0.1-52b"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < cfg.param_count()


def test_supported_shapes():
    assert "long_500k" in supported_shapes(get_config("falcon-mamba-7b"))
    assert "long_500k" in supported_shapes(get_config("jamba-v0.1-52b"))
    assert "long_500k" not in supported_shapes(get_config("qwen3-4b"))
    for arch in ASSIGNED_ARCHS:
        assert "train_4k" in supported_shapes(get_config(arch))


def test_scan_period_detection():
    assert get_config("qwen3-4b").scan_period() == 1
    assert get_config("gemma2-9b").scan_period() == 2
    assert get_config("jamba-v0.1-52b").scan_period() == 8
    from repro.models.transformer import plan_stack
    plan = plan_stack(get_config("deepseek-moe-16b"))
    assert len(plan.prefix_specs) == 1 and not plan.prefix_specs[0].moe
    assert plan.n_blocks == 27


@pytest.mark.parametrize("arch,period,blocks", [
    ("jamba2-3b", 14, 2),        # attention at 7 of each period of 14
    ("jamba-v0.1-52b", 8, 4),    # attention every 8, MoE every 2
    ("qwen3-4b", 1, 36),
])
def test_stack_plan_takes_its_periods_from_the_config(arch, period, blocks):
    """The scan period comes from the configuration's own periods, so a
    period outside (1, 2, 4, 8, 16) still scans instead of unrolling."""
    from repro.models.transformer import plan_stack

    cfg = get_config(arch)
    assert cfg.layer_periods()[-1] % period == 0
    plan = plan_stack(cfg)
    assert cfg.scan_period() == len(plan.period_specs) == period
    assert plan.prefix_specs == () and plan.n_blocks == blocks
