"""The hybrid module (``bench/archs/jamba2.py``) keeps to the harness's
contract, and its counts are the ones worked out by hand for
AI21-Jamba2-3B (d 2560, 20 heads and 1 KV head of 128, d_ff 8192, 28
layers of which layers 7 and 21 are attention, vocabulary 65536, tied
head; Mamba-1 with d_inner 5120, d_state 16, d_conv 4, dt_rank 160).

Per Mamba mixer a token multiplies through 2560*10240 + 5120*(160 + 32) +
160*5120 + 5120*2560 = 41123840 weights, which with the conv (5120*4 +
5120), dt's bias (5120), A (5120*16), D (5120) and the dt/B/C norms (160
+ 32) make 41241792 parameters.  An attention layer holds 2560*128*(2*20 +
2*1) = 13762560 and costs 4*2*20*128 = 20480 operations per key over the
two layers; every layer's MLP holds 3*2560*8192 = 62914560.
"""
import json

import pytest

import run
from archs import CONTRACT
from conftest import BENCH

FILE = "bench/configs/jamba2-3b.json"


def _conf():
    return json.loads((BENCH.parent / FILE).read_text())


@pytest.fixture(scope="module")
def mod():
    return run.load_harness(_conf(), FILE)


@pytest.fixture(scope="module")
def jamba():
    conf = _conf()
    return {**conf["used"], **conf["architecture"]}


def test_the_file_holds_every_published_number_at_its_top_level():
    # The source's config.json keys stand at the top level of the file
    # under their own names, as published; ``reduced`` is empty, so the
    # sizes run are the same.
    conf = _conf()
    assert conf["reduced"] == [] and conf["used"] == conf["published"]
    assert {k: conf.get(k, "missing") for k in conf["published"]} \
        == conf["published"]


def test_the_file_names_this_module(mod):
    assert all(callable(getattr(mod, n)) for n in CONTRACT)
    assert mod.__file__ == str(BENCH / "archs" / "jamba2.py")


def test_parameters(mod, jamba):
    assert mod.attention_params(jamba) == 13762560
    assert mod.mamba_matmul_params(jamba) == 41123840
    assert mod.mamba_params(jamba) == 41241792
    # 28 * (62914560 + 2*2560) + 2 * 13762560 + 26 * 41241792
    # + 65536*2560 (one embedding, tied) + 2560
    assert mod.param_count(jamba) == 3029337472


def test_handoff_bytes(mod, jamba):
    # state: 26 layers * 5120 * (16 * 4 B + 3 * 2 B); KV: 2 layers * K,V *
    # 1 head * 128 * 2 B a token
    assert mod.state_bytes(jamba) == 9318400
    assert mod.kv_bytes_per_token(jamba) == 1024
    assert mod.handoff_bytes(jamba, 2048) == 2097152 + 9318400


def test_prefill_flops(mod, jamba):
    # per token: 2 * (28*3*2560*8192 + 2*13762560 + 26*41123840) matmul
    # operations plus 26 * (2*4*5120 conv + 7*5120*16 scan)
    per_token = 2 * 2858352640 + 26 * 614400
    assert mod._token_flops(jamba) == per_token == 5732679680
    # 2048 tokens, 20480 per key over 2048*2049/2 causal keys, one head
    assert mod.prefill_flops(jamba, 2048) == (
        2048 * per_token + 20480 * 2098176 + 2 * 2560 * 65536)
    assert mod.prefill_flops(jamba, 2048) == 11783834173440


def test_decode_flops_and_bytes(mod, jamba):
    # two live slots writing positions 600 and 700
    assert mod.decode_flops(jamba, [600, 700]) == (
        2 * (5732679680 + 2 * 2560 * 65536) + 20480 * (601 + 701))
    # every weight once in bf16 (the tied embedding as the unembedding),
    # (601 + 701) KV rows of 1024 B, and each slot's state read and written
    assert mod.decode_bytes(jamba, [600, 700]) == (
        3029337472 * 2 + 1302 * 1024 + 4 * 9318400)
    assert mod.decode_bytes(jamba, [600, 700]) == 6097281792


@pytest.mark.parametrize("block,key,value", [
    (None, None, None),
    ("used", "mamba_d_state", 8),
    ("used", "attn_layer_period", 8),
    ("used", "num_key_value_heads", 8),
    ("architecture", "ssm_norms", False),
    ("architecture", "positional_encoding", "rope"),
])
def test_check_fails_on_a_changed_size(mod, block, key, value):
    from repro.configs import get_config

    conf = _conf()
    cfg = get_config(conf["registry_name"])
    if block is None:
        assert mod.check(cfg, conf["used"], conf["architecture"]) == {}
        assert mod.check(get_config(conf["registry_name"] + "-reduced"),
                         conf["rehearsal"], conf["architecture"]) == {}
        return
    conf[block][key] = value
    bad = mod.check(cfg, conf["used"], conf["architecture"])
    assert set(bad) == {key} and bad[key][1] == value


def test_a_dense_model_is_not_this_architecture(mod):
    from repro.configs import get_config

    conf = _conf()
    bad = mod.check(get_config("qwen3-4b"), conf["used"],
                    conf["architecture"])
    assert "family" in bad and "hidden_size" not in bad


def test_warm_handoff_is_in_the_programs_form(mod):
    """The warm-up's stand-in: K and V of the attention layers only, and a
    state for each SSM layer, which the program's ``KVCache`` takes as its
    third field."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.kvcache import KVCache, handoff_bytes

    cfg = get_config("jamba2-3b-reduced")
    kv = KVCache(*mod.warm_handoff(cfg, 32))
    assert kv.k.shape == kv.v.shape == (1, 1, 32, 128)
    assert kv.state["ssm"].shape == (13, 1024, 16)
    assert kv.state["conv"].shape == (13, 1024, 3)
    assert kv.state["ssm"].dtype == np.float32
    assert kv.nbytes_wire() == handoff_bytes(cfg, 32)
