"""The FLOP counts equal hand sums for both configurations."""

import os

import pytest

from perfbench.flops import encoder_classifier as fl
from perfbench.lib.manifest import BENCH_DIR, load_json

TRAFFIC = load_json(os.path.join(BENCH_DIR, "traffic", "finetune.json"))


def _config(name):
    return load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))


def test_bert_base_by_hand():
    # per token and layer, forward: q,k,v,o 4*2*768^2; mlp 2*2*768*3072;
    # scores and values 2*(2*128*768)
    per_token_layer = 4 * 2 * 768 ** 2 + 2 * 2 * 768 * 3072 + 2 * 2 * 128 * 768
    assert per_token_layer == 14_548_992
    forward = 128 * 12 * per_token_layer + 2 * 768 * 768 + 2 * 768 * 2
    assert fl.tokens_per_sample(_config("bert_base"), TRAFFIC) == 128
    assert fl.train_flops_per_sample(_config("bert_base"), TRAFFIC) == 3 * forward
    assert 3 * forward == pytest.approx(67.05e9, rel=1e-3)


def test_vit_b16_by_hand():
    per_token_layer = 4 * 2 * 768 ** 2 + 2 * 2 * 768 * 3072 + 2 * 2 * 197 * 768
    encoder = 197 * 12 * per_token_layer
    patch = 196 * 2 * (16 * 16 * 3) * 768
    head = 2 * 768 * 10
    assert fl.tokens_per_sample(_config("vit_b16"), TRAFFIC) == 197
    # pixels need no gradient: the patch embedding counts twice, the rest thrice
    assert fl.train_flops_per_sample(_config("vit_b16"), TRAFFIC) \
        == 3 * (encoder + head) + 2 * patch
    assert 3 * (encoder + head) + 2 * patch == pytest.approx(105.1e9, rel=2e-3)
