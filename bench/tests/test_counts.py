"""Operation and byte counts of the configurations' architecture modules
against hand counts."""
from __future__ import annotations

import pathlib

import pytest

from bench import harness

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return harness.load_config(CONFIGS / f"{name}.json")


# Hand counts.  qwen2-1.5b, per layer: wq 1536x1536 + wk, wv 1536x256 + wo
# 1536x1536 = 5,505,024; gate, up, down 3 x 1536 x 8960 = 41,287,680.
# Head (tied) 151,936 x 1536 = 233,373,696.  q/k/v bias 1536 + 256 + 256.
# Total with norms, 1,543,714,304, is Qwen2-1.5B's published count.
# internlm2-1.8b, per layer: wq, wo 2048x2048 + wk, wv 2048x1024 =
# 12,582,912; gate, up, down 3 x 2048 x 8192 = 50,331,648.  Head (untied)
# 2048 x 92,544 = 189,530,112; no bias.
HAND = {
    "qwen2-1.5b": dict(layer=5_505_024 + 41_287_680, head=233_373_696,
                       bias=2048, layers=28, d=1536, kv_tok=28 * 2 * 2 * 128 * 2,
                       hq=12, dh=128),
    "internlm2-1.8b": dict(layer=12_582_912 + 50_331_648, head=189_530_112,
                           bias=0, layers=24, d=2048,
                           kv_tok=24 * 2 * 8 * 128 * 2, hq=16, dh=128),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_weights(name):
    c, h = cfg(name), HAND[name]
    arch = harness.arch(c)
    assert arch.layer_matmul_params(c) == h["layer"]
    assert arch.head_params(c) == h["head"]
    norms = (2 * h["layers"] + 1) * h["d"]
    want = (h["layers"] * (h["layer"] + h["bias"]) + h["head"]) * 2 + norms * 4
    assert arch.weight_bytes(c) == want
    assert arch.kv_bytes_per_token(c) == h["kv_tok"]


def test_qwen_total_matches_published_count():
    c, h = cfg("qwen2-1.5b"), HAND["qwen2-1.5b"]
    total = (h["layers"] * (h["layer"] + h["bias"]) + h["head"]
             + (2 * h["layers"] + 1) * h["d"])
    assert total == 1_543_714_304


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode(name):
    c, h = cfg(name), HAND[name]
    arch = harness.arch(c)
    per_tok = 2 * (h["layers"] * h["layer"] + h["head"])
    attn = lambda keys: h["layers"] * 4 * h["hq"] * h["dh"] * keys
    lens = [0, 100, 4000]
    assert arch.decode_flops(c, lens) == 3 * per_tok + attn(1 + 101 + 4001)
    assert arch.decode_bytes(c, lens) == (arch.weight_bytes(c)
                                          + (1 + 101 + 4001) * h["kv_tok"])
