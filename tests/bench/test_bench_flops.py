"""bench/flops.py against counts made by hand for the two configurations."""

import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                       "configs")


def model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["model"]


def test_olmo_1b_parameters_and_bytes():
    m = model("olmo-1b")
    # per layer: q, k, v, o at 2048 x 16 x 128, three 2048 x 8192 FFN
    per_layer = 4 * 2048 * 16 * 128 + 3 * 2048 * 8192
    assert flops.layer_params(m) == per_layer == 67_108_864
    assert flops.matmul_params(m) == 16 * per_layer == 2 ** 30
    # tied: one 50304 x 2048 table
    assert flops.params(m) == 2 ** 30 + 50304 * 2048 == 1_176_764_416
    assert flops.weight_bytes(m) == 2_353_528_832           # 2.35 GB
    assert flops.kv_bytes_per_token(m) == 2 * 16 * 16 * 128 * 2 == 131072


def test_phi3_parameters_and_bytes():
    m = model("phi-3-vision-4.2b")
    per_layer = 4 * 3072 * 32 * 96 + 3 * 3072 * 8192
    assert flops.layer_params(m) == per_layer == 113_246_208
    # untied: embedding table and head, 32064 x 3072 each
    assert flops.params(m) == 32 * per_layer + 2 * 32064 * 3072 \
        == 3_820_879_872
    assert flops.weight_bytes(m) == 7_641_759_744           # 7.64 GB
    assert flops.kv_bytes_per_token(m) == 2 * 32 * 32 * 96 * 2 == 393216


@pytest.mark.parametrize("name,L,H,hd", [("olmo-1b", 16, 16, 128),
                                         ("phi-3-vision-4.2b", 32, 32, 96)])
def test_forward_flops_per_token(name, L, H, hd):
    m = model(name)
    P = flops.matmul_params(m)
    # one token at position 0 attends to itself: 4 L H hd
    assert flops.sequence_flops(m, 1) == 2 * P + 4 * L * H * hd
    # three tokens attend to 1 + 2 + 3 positions
    assert flops.sequence_flops(m, 3) == 3 * 2 * P + 4 * L * H * hd * 6
    V, d = m["vocab_size"], m["hidden_size"]
    # a request of 10 prompt and 4 served tokens runs 13 positions and
    # 4 sets of logits; an embedding of 5 bytes runs 5 and no logits
    assert flops.generation_flops(m, [(10, 4)]) == \
        flops.sequence_flops(m, 13) + 4 * 2 * d * V
    assert flops.embed_flops(m, [5]) == flops.sequence_flops(m, 5)


def test_decode_and_prefill_bytes():
    m = model("olmo-1b")
    w, kv = 2_353_528_832, 131072
    # decode over 4 slots holding 1000 cached tokens in all
    assert flops.decode_bytes(m, 1000, 4) == w + kv * 1004
    # one 32-token chunk after 64 cached tokens
    assert flops.prefill_bytes(m, 64, 32) == w + kv * 96
    p = model("phi-3-vision-4.2b")
    assert flops.decode_bytes(p, 0, 4) == 7_641_759_744 + 393216 * 4


def test_scan_bytes_and_flops():
    # 262,144 x 2048 float32 (2 GiB) and 8 queries
    assert flops.scan_bytes(262144, 2048, 8) == (262144 + 8) * 2048 * 4
    assert flops.scan_bytes(262144, 2048, 0) == 2 ** 31
    assert flops.scan_flops(262144, 2048, 8) == 2 * 262144 * 2048 * 8
