"""Operation and byte counts for qwen3-4b, against numbers worked out by
hand from its sizes (d 2560, 32 heads and 8 KV heads of 128, d_ff 9728,
36 layers, vocabulary 151936, untied head).

Per layer a token multiplies through 2560*128*(2*32 + 2*8) + 3*2560*9728
= 26214400 + 74711040 = 100925440 weights.  Attention costs
4*36*32*128 = 589824 operations per key.
"""
import json

import pytest

import counts
from conftest import BENCH


@pytest.fixture(scope="module")
def qwen():
    conf = json.loads((BENCH / "configs" / "qwen3-4b.json").read_text())
    return {**conf["used"], **conf["architecture"]}


def test_parameters(qwen):
    assert counts.matmul_params_per_layer(qwen) == 100925440
    # 36 * (100925440 + 2*2560 + 2*128) + 2 * 151936*2560 + 2560, the
    # count the program itself reported on the chip
    assert counts.param_count(qwen) == 4411424256
    assert counts.kv_bytes_per_token(qwen) == 147456      # 144 KiB


def test_prefill_flops(qwen):
    # 2*512*36*100925440 + 589824 * 512*513/2 + 2*2560*151936
    assert counts.prefill_flops(qwen, 512) == (
        3720515420160 + 77460406272 + 777912320)
    assert counts.prefill_flops(qwen, 512) == 3798753738752


def test_decode_flops_and_bytes(qwen):
    # two live slots writing positions 600 and 700:
    # 2 * (2*36*100925440 + 2*2560*151936) + 589824 * (601 + 701)
    assert counts.decode_flops(qwen, [600, 700]) == 16857038848
    # weights once, without the embedding rows (gathered, not streamed):
    # (4411424256 - 151936*2560) * 2 bytes, plus (601 + 701) KV rows
    assert counts.decode_bytes(qwen, [600, 700]) == (
        8044936192 + 1302 * 147456)
    assert counts.decode_bytes(qwen, [600, 700]) == 8236923904


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peak_for("TPU v99")
    assert counts.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
