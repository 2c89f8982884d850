"""The seam between the harness and an architecture: a configuration file
names its module under ``bench/archs/``, and the size check, the reference
and the counts are reached only through it.

The dense module's counts are checked against numbers worked out by hand
for qwen3-4b (d 2560, 32 heads and 8 KV heads of 128, d_ff 9728, 36
layers, vocabulary 151936, untied head).  Per layer a token multiplies
through 2560*128*(2*32 + 2*8) + 3*2560*9728 = 26214400 + 74711040 =
100925440 weights.  Attention costs 4*36*32*128 = 589824 operations per
key.
"""
import json
from types import SimpleNamespace

import numpy as np
import pytest

import run
from archs import CONTRACT
from conftest import BENCH
from reference import Served

DATA = BENCH / "tests" / "data"
QWEN_FILE = "bench/configs/qwen3-4b.json"
FIXTURE_FILE = "bench/tests/data/fixture_config.json"


def _conf(path):
    return json.loads((BENCH.parent / path).read_text())


@pytest.fixture(scope="module")
def dense():
    return run.load_harness(_conf(QWEN_FILE), QWEN_FILE)


@pytest.fixture(scope="module")
def qwen():
    conf = _conf(QWEN_FILE)
    return {**conf["used"], **conf["architecture"]}


def test_parameters(dense, qwen):
    assert dense.matmul_params_per_layer(qwen) == 100925440
    # 36 * (100925440 + 2*2560 + 2*128) + 2 * 151936*2560 + 2560, the
    # count the program itself reported on the chip
    assert dense.param_count(qwen) == 4411424256
    assert dense.kv_bytes_per_token(qwen) == 147456      # 144 KiB


def test_prefill_flops(dense, qwen):
    # 2*512*36*100925440 + 589824 * 512*513/2 + 2*2560*151936
    assert dense.prefill_flops(qwen, 512) == (
        3720515420160 + 77460406272 + 777912320)
    assert dense.prefill_flops(qwen, 512) == 3798753738752


def test_decode_flops_and_bytes(dense, qwen):
    # two live slots writing positions 600 and 700:
    # 2 * (2*36*100925440 + 2*2560*151936) + 589824 * (601 + 701)
    assert dense.decode_flops(qwen, [600, 700]) == 16857038848
    # weights once, without the embedding rows (gathered, not streamed):
    # (4411424256 - 151936*2560) * 2 bytes, plus (601 + 701) KV rows
    assert dense.decode_bytes(qwen, [600, 700]) == (
        8044936192 + 1302 * 147456)
    assert dense.decode_bytes(qwen, [600, 700]) == 8236923904


def test_handoff_bytes(dense, qwen):
    assert dense.handoff_bytes(qwen, 512) == 512 * 147456


@pytest.mark.parametrize("block,key,value", [
    (None, None, None),
    ("used", "hidden_size", 2048),
    ("used", "num_key_value_heads", 4),
    ("architecture", "qk_norm", False),
])
def test_dense_check_fails_on_a_changed_size(dense, block, key, value):
    from repro.configs import get_config

    conf = _conf(QWEN_FILE)
    cfg = get_config(conf["registry_name"])
    if block is None:
        assert dense.check(cfg, conf["used"], conf["architecture"]) == {}
        assert dense.check(get_config(conf["registry_name"] + "-reduced"),
                           conf["rehearsal"], conf["architecture"]) == {}
        return
    conf[block][key] = value
    bad = dense.check(cfg, conf["used"], conf["architecture"])
    assert set(bad) == {key} and bad[key][1] == value


def test_warm_handoff_is_a_random_kv_of_every_layer(dense):
    """The warm-up's stand-in hand-off: K then V, (layers, KV heads,
    prompt, head size), float32 normals from a fixed seed."""
    cfg = SimpleNamespace(num_layers=3, kv_heads=2, resolved_head_dim=4)
    k, v = dense.warm_handoff(cfg, 5)
    assert k.shape == v.shape == (3, 2, 5, 4) and k.dtype == np.float32
    rng = np.random.default_rng(0)
    for got in (k, v):
        np.testing.assert_array_equal(
            got, rng.standard_normal(got.shape).astype(np.float32))


def _cell(conf, harness):
    return SimpleNamespace(config=conf, config_entry={"file": FIXTURE_FILE},
                           harness=harness, mix={}, model=conf["used"])


def test_a_new_architecture_is_only_new_files(capsys):
    """A configuration under tests/data names a module there; the
    harness's size check, comparison and counts call that module."""
    conf = _conf(FIXTURE_FILE)
    mod = run.load_harness(conf, FIXTURE_FILE, where=DATA)
    assert mod.__file__ == str(DATA / "fixture_arch.py")
    cell = _cell(conf, mod)
    run.check_sizes(cell, SimpleNamespace(name="fixture"), False)
    served = [Served(prompt=np.zeros(4, np.int32),
                     tokens=np.zeros(3, np.int32))]
    res = SimpleNamespace(sample=served, rows=[], recorder=None)
    assert run.compare(cell, res, 7000, False, True) == (7.0, 8.0)
    assert run.Context(cell, res, None, None).counts is mod

    conf["used"]["differs_width"] = 9
    with pytest.raises(SystemExit):
        run.check_sizes(cell, SimpleNamespace(name="fixture"), False)
    assert FIXTURE_FILE in capsys.readouterr().err


def test_a_missing_module_fails_naming_the_file(capsys):
    conf = dict(_conf(FIXTURE_FILE), harness="no_such_arch")
    with pytest.raises(SystemExit):
        run.load_harness(conf, FIXTURE_FILE, where=DATA)
    err = capsys.readouterr().err
    assert FIXTURE_FILE in err and "no_such_arch.py" in err
    del conf["harness"]
    with pytest.raises(SystemExit):
        run.load_harness(conf, FIXTURE_FILE, where=DATA)
    assert FIXTURE_FILE in capsys.readouterr().err


def test_a_module_lacking_a_contract_name_fails_naming_it(tmp_path, capsys):
    (tmp_path / "partial.py").write_text("".join(
        f"def {n}(*args):\n    pass\n" for n in CONTRACT
        if n != "decode_bytes"))
    conf = dict(_conf(FIXTURE_FILE), harness="partial")
    with pytest.raises(SystemExit):
        run.load_harness(conf, FIXTURE_FILE, where=tmp_path)
    err = capsys.readouterr().err
    assert str(tmp_path / "partial.py") in err and "decode_bytes" in err
