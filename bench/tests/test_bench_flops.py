"""Model FLOPs per token and the table of peaks."""

import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parent.parent / "configs"


def published(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["published"]


def test_qwen3_hand_count():
    # per layer: q,k,v,o 2*2048*(16+8+8+16)*128 = 25,165,824
    #            causal attention 2*2*16*128*4097/2 = 16,781,312
    #            SwiGLU 2*3*2048*6144 = 75,497,472
    # 6 layers 704,667,648; head 2*2048*151936 = 622,329,856
    fwd = 6 * (25_165_824 + 16_781_312 + 75_497_472) + 622_329_856
    assert fwd == 1_326_997_504
    c = published("qwen3-1.7b")
    assert flops.forward_flops_per_token(c, 4096) == fwd
    assert flops.train_flops_per_token(c, 4096) == 3 * fwd


def test_olmoe_hand_count():
    # per layer: q,k,v,o 2*2048*(16+16+16+16)*128 = 33,554,432
    #            causal attention 16,781,312
    #            router 2*2048*64 = 262,144; 8 routed experts
    #            8*2*3*2048*1024 = 100,663,296
    # 4 layers; head 2*2048*50304 = 206,045,184
    fwd = 4 * (33_554_432 + 16_781_312 + 262_144 + 100_663_296) + \
        206_045_184
    c = published("olmoe-1b-7b")
    assert flops.forward_flops_per_token(c, 4096) == fwd == 811_089_920


def test_only_routed_experts_count():
    c = published("olmoe-1b-7b")
    more = dict(c, num_experts=128)          # twice the experts, same top-8
    d = flops.forward_flops_per_token(more, 4096) - \
        flops.forward_flops_per_token(c, 4096)
    assert d == c["num_hidden_layers"] * 2 * 2048 * 64   # the router only
    wider = dict(c, num_experts_per_tok=9)
    d = flops.forward_flops_per_token(wider, 4096) - \
        flops.forward_flops_per_token(c, 4096)
    assert d == c["num_hidden_layers"] * 2 * 3 * 2048 * 1024


def test_peaks():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
