"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed here and compiles for a topology that is
only described, so these tests catch what interpret mode cannot: Mosaic
layouts it refuses (the int4 nibble interleave), fast-memory limits, and a
serving step that does not fit one chip's HBM.  Nothing runs, so nothing
here says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K

HBM_BYTES = 16e9        # one v5e chip
HEAD_DIM, KV_HEADS, GQ, PAGE = 128, 8, 4, 16   # serving widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, bits, s):
    """(fn, arg specs) of one kernel op at serving widths, compiled as a
    Mosaic kernel (interpret=False)."""
    d, group, t = HEAD_DIM, 64, 2048       # 256 tokens x 8 kv heads
    cw = d if bits == 8 else d // 2
    cdt = jnp.int8 if bits == 8 else jnp.uint8
    b, seq, w = 8, 512, 4
    pps = seq // PAGE
    n_pages = b * pps + 1
    kw = dict(bits=bits, group=group, interpret=False)
    if name == "quant_pack":
        return (functools.partial(K.quant_pack_op, **kw),
                [s((t, d), jnp.bfloat16)])
    if name == "dequant_unpack":
        return (functools.partial(K.dequant_unpack_op, **kw),
                [s((t, cw), cdt), s((t, d // group), jnp.float32)])
    if name == "hadamard":
        return (functools.partial(K.hadamard_op, interpret=False),
                [s((t, d), jnp.bfloat16)])
    if name == "decode_attention":
        kv = [s((b, KV_HEADS, seq, cw), cdt),
              s((b, KV_HEADS, seq, d // group), jnp.float32)] * 2
        return (lambda *a: K.decode_attention_op(*a[:5], kv_len=a[5], **kw),
                [s((b, KV_HEADS, GQ, d), jnp.bfloat16), *kv,
                 s((b,), jnp.int32)])
    pool = [s((n_pages, KV_HEADS, PAGE, cw), cdt),
            s((n_pages, KV_HEADS, PAGE, d // group), jnp.float32)] * 2
    tables = [s((b, pps), jnp.int32), s((b,), jnp.int32)]
    if name == "paged_attention":
        return (functools.partial(K.paged_attention_op, **kw),
                [s((b, KV_HEADS, GQ, d), jnp.bfloat16), *pool, *tables])
    assert name == "paged_verify_attention"
    return (functools.partial(K.paged_verify_attention_op, **kw),
            [s((b, KV_HEADS, w, GQ, d), jnp.bfloat16), *pool, *tables])


@pytest.mark.parametrize("name,bits", [
    (n, b) for n in ("quant_pack", "dequant_unpack", "decode_attention",
                     "paged_attention", "paged_verify_attention")
    for b in (8, 4)] + [("hadamard", 8)])
def test_kernel_compiles_for_v5e(one_chip, name, bits):
    fn, specs = _kernel_case(name, bits, functools.partial(_spec, one_chip))
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_4b_serving_steps_fit_one_v5e(one_chip):
    """Prefill, dense arena decode and paged arena decode of qwen3-4b at
    its registered widths, in bf16, each compiled for one v5e chip and
    within its HBM."""
    from repro.configs import get_config
    from repro.core.quality import _jitted_steps, _paged_steps, init_paged_pools
    from repro.models import init_cache, init_params
    from repro.serving.workers import RuntimeConfig

    cfg = get_config("qwen3-4b")
    rc = RuntimeConfig()
    seq, max_len, slots = rc.seq, rc.arena_max_len, 8
    pps = -(-max_len // PAGE)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = place(init_params(cfg, abstract=True, dtype=jnp.bfloat16)[0])
    tok = functools.partial(_spec, one_chip, dtype=jnp.int32)
    pre, _, arena = _jitted_steps(cfg.name, seq, 1, max_len)
    paged, _ = _paged_steps(cfg.name, PAGE)
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    pools = place(jax.eval_shape(
        lambda: init_paged_pools(cfg, slots * pps + 1, PAGE, group=1)))
    steps = {
        "prefill": pre.lower(params, {"tokens": tok((1, seq))}),
        "arena_decode": arena.lower(
            params, cache, tok((slots, 1)), tok((slots,)),
            _spec(one_chip, (slots,), jnp.bool_)),
        "paged_decode": paged.lower(
            params, *pools, tok((slots, pps)), tok((slots,)),
            tok((slots, 1)), tok((slots,)),
            _spec(one_chip, (slots,), jnp.bool_)),
    }
    for name, lowered in steps.items():
        ma = lowered.compile().memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        assert ma.argument_size_in_bytes > 8e9, (name, ma)   # bf16 weights
        assert total < HBM_BYTES, (name, total)
