"""Operation and byte counts against hand counts for both
configurations."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import flops, weights  # noqa: E402


def spec(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return weights.spec_of(json.load(f))


# (name, weights per layer, layers, vocab x d, whole-model bytes as served)
HAND = [
    # q 1536^2 + k,v 2*1536*256 + o 1536^2 + gate,up,down 3*1536*8960
    ("qwen2-1.5b", 46_792_704, 28, 151936 * 1536),
    # q 5120^2 + k,v 2*5120*1024 + o 5120^2 + 3*5120*17408
    ("qwen3-14b", 330_301_440, 8, 151936 * 5120),
]


@pytest.mark.parametrize("name,per_layer,layers,table", HAND)
def test_layer_weights(name, per_layer, layers, table):
    s = spec(name)
    assert flops.layer_matmul_params(s) == per_layer
    assert s["layers"] == layers and s["vocab"] * s["d"] == table


@pytest.mark.parametrize("name,per_layer,layers,table", HAND)
def test_prefill_and_decode_flops(name, per_layer, layers, table):
    s = spec(name)
    H, hd = s["heads"], s["head_dim"]
    # one token: 2 FLOPs per weight per layer, q.k and p.v over 1 key, head
    one = layers * (2 * per_layer + 4 * H * hd) + 2 * table
    assert flops.prefill_flops(s, 1) == one
    assert flops.decode_flops(s, 1) == one
    # 1000 prompt tokens: causal attention sums keys 1..1000 = 500500
    n = 1000
    want = layers * (2 * per_layer * n + 4 * H * hd * 500_500) + 2 * table
    assert flops.prefill_flops(s, n) == want
    assert flops.decode_flops(s, 4096) - flops.decode_flops(s, 4095) == \
        layers * 4 * H * hd


def test_decode_bytes():
    s = spec("qwen2-1.5b")
    # 28 x (matmul weights bf16 + 2 norm scales f32 + q,k,v biases bf16)
    # + final norm f32 + the tied table as the head
    layer = 46_792_704 * 2 + 2 * 1536 * 4 + (1536 + 2 * 256) * 2
    assert flops.weight_bytes_per_step(s) == \
        28 * layer + 1536 * 4 + 151936 * 1536 * 2
    # K and V, 28 layers, 2 kv heads of 128, bf16
    assert flops.kv_bytes(s, 1) == 2 * 28 * 2 * 128 * 2
    q = spec("qwen3-14b")
    layer = 330_301_440 * 2 + 2 * 5120 * 4 + 2 * 128 * 4
    assert flops.weight_bytes_per_step(q) == \
        8 * layer + 5120 * 4 + 151936 * 5120 * 2
    assert flops.kv_bytes(q, 4096) == 2 * 8 * 8 * 128 * 2 * 4096


@pytest.mark.parametrize("name,served", [("qwen2-1.5b", 3_087_603_712),
                                         ("qwen3-14b", 8_396_828_672)])
def test_weights_as_served(name, served):
    # compiled on a described v5e, bench/aot_memory.py reads these bytes
    assert weights.param_bytes(spec(name)) == served
