"""Each cell's prefill and arena-decode programs compile for one described
v5e chip at the configuration's real widths, with the cell's slot count,
and fit its memory.  Nothing runs; the topology is described inside a
fixture, never at import (one process at a time may load the TPU
library)."""
import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH, ROOT

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"]]


@pytest.mark.parametrize("workload", _cells())
def test_cell_programs_compile_for_v5e(one_chip, workload):
    from repro.configs import get_config
    from repro.core.quality import _jitted_steps
    from repro.models import init_cache, init_params

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    conf = json.loads((ROOT / next(c["file"] for c in spec["configs"]
                                   if c["name"] == cell["config"]))
                      .read_text())
    cfg = get_config(conf["registry_name"])
    seq, slots = mix["prompt_tokens"], mix["slots"]
    max_len = seq + mix["output_tokens"] + 2

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def tok(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = place(init_params(cfg, abstract=True, dtype=jnp.bfloat16)[0])
    mask = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    pre, _, arena = _jitted_steps(cfg.name, seq, 1, max_len)
    steps = {"prefill": pre.lower(params, {"tokens": tok((1, seq))})}
    cache = place(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    steps["arena_decode"] = arena.lower(params, cache, tok((slots, 1)),
                                        tok((slots,)), mask)
    for name, lowered in steps.items():
        ma = lowered.compile().memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(workload, name, total / 1e9, "GB")
        assert total < HBM_BYTES, (workload, name, total)
