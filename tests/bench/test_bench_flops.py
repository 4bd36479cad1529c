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


def moonlight():
    """A Moonlight-16B-A3B block as published, holding 8 of its 64
    routed experts (one chip's share of 8-way expert parallelism), and
    its ``published`` block."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "moonlight_block.json")) as f:
        conf = json.load(f)
    return conf["model"], conf["published"]


# counts made by hand: d 2048, 16 heads, MLA (kv_lora_rank 512, nope 128,
# rope 64, v 128, no q_lora_rank), layer 0 dense (11264), 26 expert
# layers (router over 64, 2 shared and 8 held experts of 1408, 6 a token)
ATTN = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
OUTSIDE = ATTN + 2048 * 64 + 2 * 3 * 2048 * 1408
HELD = 8 * 3 * 2048 * 1408


@pytest.mark.parametrize("what,count,value", [
    ("attention", lambda m, p: flops.attention_params(m, p), 13_762_560),
    ("layer 0", lambda m, p: flops.layer_params(m, 0, p), 82_968_576),
    ("expert layer", lambda m, p: flops.layer_params(m, 1, p), 100_401_152),
    ("outside the experts",
     lambda m, p: flops.layer_params(m, 26, p) - flops.expert_params(m),
     31_195_136),
    ("experts held", lambda m, p: flops.expert_params(m), 69_206_016),
    ("embedding and head",
     lambda m, p: flops.params(m, p) - flops.matmul_params(m, p),
     671_088_640),
    ("params", lambda m, p: flops.params(m, p), 3_364_487_168),
    ("weight bytes", lambda m, p: flops.weight_bytes(m, published=p),
     6_728_974_336),
    ("kv bytes per token", lambda m, p: flops.kv_bytes_per_token(m),
     27 * (512 + 64) * 2),
    ("weight flops per token", lambda m, p: 2 * flops.token_params(m, p),
     2_125_463_552),
    ("attention flops per position and layer",
     lambda m, p: flops.attention_flops_per_position(m), 10_240),
])
def test_moonlight_shaped_block_counts(what, count, value):
    m, published = moonlight()
    assert count(m, published) == value


def test_moonlight_forward_flops_and_bytes():
    m, p = moonlight()
    assert (ATTN, OUTSIDE, HELD) == (13_762_560, 31_195_136, 69_206_016)
    assert 82_968_576 + 26 * (OUTSIDE + HELD) + 2 * 163840 * 2048 \
        == 3_364_487_168
    # a token meets 6 * 8 / 64 of its routed experts here
    per_token = 82_968_576 + 26 * (OUTSIDE + 6 * 8 / 64 * 3 * 2048 * 1408)
    assert 2 * per_token == 2_125_463_552
    assert flops.sequence_flops(m, 3, p) == \
        3 * 2_125_463_552 + 27 * 10_240 * 6
    assert flops.generation_flops(m, [(10, 4)], p) == \
        flops.sequence_flops(m, 13, p) + 4 * 2 * 2048 * 163840
    assert flops.decode_bytes(m, 1000, 4, p) == \
        6_728_974_336 + 31_104 * 1004
    assert flops.prefill_bytes(m, 64, 32, p) == \
        6_728_974_336 + 31_104 * 96
    # without the published count the router would be read as 8 wide,
    # and every token would meet all 6 of its experts here
    assert flops.params(m) == 3_364_487_168 - 26 * 2048 * 56
    assert flops.layer_token_params(m, 1) == OUTSIDE - 2048 * 56 + HELD * 6 / 8


def test_all_64_experts_held():
    m, _ = moonlight()
    m = dict(m, n_routed_experts=64)
    assert flops.expert_params(m) == 64 * 3 * 2048 * 1408 == 553_648_128
    assert flops.layer_params(m, 1, {"n_routed_experts": 64}) == \
        31_195_136 + 553_648_128
    # all held: a token's 6 experts are all here
    assert flops.layer_token_params(m, 1) == 31_195_136 + 6 * 3 * 2048 * 1408


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("sliding_window", 4096),
    ("ssm_state_size", 128),
    ("mamba_d_conv", 4),
    ("conv_kernel", 4),
    ("linear_num_key_heads", 16),
    ("num_local_experts", 8),
    ("index_topk", 2048),
    ("moe_layer_freq", 2),
])
def test_a_mechanism_the_count_does_not_model_raises(key, value):
    for m in (model("olmo-1b"), moonlight()[0]):
        with pytest.raises(ValueError, match=key):
            flops.params(dict(m, **{key: value}))
        with pytest.raises(ValueError, match=key):
            flops.generation_flops(dict(m, **{key: value}), [(4, 2)])


def test_switched_off_mechanisms_count_as_absent():
    m = model("olmo-1b")
    for off in ({"sliding_window": None}, {"layer_types": []},
                {"num_local_experts": 0},
                {"sliding_window": 4096, "use_sliding_window": False}):
        assert flops.params(dict(m, **off)) == 1_176_764_416
    # head_dim falls back to hidden_size / num_attention_heads
    m.pop("head_dim")
    assert flops.layer_params(m) == 67_108_864
