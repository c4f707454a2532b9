"""The FLOPs and bytes functions against sizes worked out by hand."""

import json
from pathlib import Path

import pytest

from chipbench import costs, spec

ROOT = Path(__file__).resolve().parents[2]

GIB = 2**30


def sizes_of(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return spec.sizes(json.loads((ROOT / entry["file"]).read_text()))


@pytest.mark.parametrize("name,gib,params,kv", [
    # 32 x (4 x 3072^2 + 3 x 3072 x 8192) + 2 x 32064 x 3072, bfloat16
    ("phi3-mini-3.8b", 7.12, 3.821e9, 32 * 2 * 32 * 96 * 2),
    # 10 x (2 x 5120 x 4096 + 2 x 5120 x 1024 + 3 x 5120 x 14336)
    #   + 2 x 131072 x 5120
    ("mistral-nemo-12b-s10", 7.58, 4.068e9, 10 * 2 * 8 * 128 * 2),
])
def test_weights_and_cache(name, gib, params, kv):
    s = sizes_of(name)
    assert round(costs.weight_bytes(s) / GIB, 2) == gib
    assert costs.param_count(s) == pytest.approx(params, rel=1e-3)
    assert costs.kv_bytes_per_token(s) == kv


def test_kv_per_token_hand_numbers():
    assert costs.kv_bytes_per_token(sizes_of("phi3-mini-3.8b")) == 384 * 1024
    assert costs.kv_bytes_per_token(sizes_of("mistral-nemo-12b-s10")) == 40960


def test_nemo_decode_step_streams_6_79_gb():
    # the layers plus the output head; one embedding row per sequence
    s = sizes_of("mistral-nemo-12b-s10")
    assert costs.decode_weight_bytes(s, 0) / 1e9 == pytest.approx(6.79,
                                                                  abs=0.01)


def test_token_and_prefill_flops():
    s = sizes_of("phi3-mini-3.8b")
    layer = 4 * 3072 * 3072 + 3 * 3072 * 8192
    head = 32064 * 3072
    assert costs.token_flops(s, 1) == 2 * (32 * layer + head) \
        + 32 * 4 * 32 * 96
    # a one-token prompt costs one decode token
    assert costs.prefill_flops(s, 1) == costs.token_flops(s, 1)
    # causal attention: positions 1..P, not P x P
    p = 100
    assert costs.prefill_flops(s, p) == pytest.approx(
        2 * 32 * layer * p + 32 * 4 * 32 * 96 * p * (p + 1) / 2 + 2 * head)


def test_least_time_takes_the_larger_bound():
    s = sizes_of("phi3-mini-3.8b")
    pk = costs.peak("TPU v5 lite")
    one = costs.decode_step_least_s(s, [1000], pk)
    assert one == pytest.approx(
        (costs.decode_weight_bytes(s, 1) + 1000 * 384 * 1024) / 819e9)
    # thousands of rows turn the step compute-bound
    many = costs.decode_step_least_s(s, [1] * 4096, pk)
    assert many == pytest.approx(costs.token_flops(s, 1) * 4096 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        costs.peak("TPU v9 imaginary")
