"""Operation and byte counts against hand counts of both configurations."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import counts

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# Hand counts.  qwen2-1.5b, per layer: wq 1536x1536 + wk, wv 1536x256 + wo
# 1536x1536 = 5,505,024; gate, up, down 3 x 1536 x 8960 = 41,287,680.
# Head (tied) 151,936 x 1536 = 233,373,696.  q/k/v bias 1536 + 256 + 256.
# Total with norms, 1,543,714,304, is Qwen2-1.5B's published count.
HAND = {
    "qwen2-1.5b": dict(layer=5_505_024 + 41_287_680, head=233_373_696,
                       bias=2048, layers=28, d=1536, kv_tok=28 * 2 * 2 * 128 * 2,
                       hq=12, dh=128),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_weights(name):
    c, h = cfg(name), HAND[name]
    assert counts.layer_matmul_params(c) == h["layer"]
    assert counts.head_params(c) == h["head"]
    norms = (2 * h["layers"] + 1) * h["d"]
    want = (h["layers"] * (h["layer"] + h["bias"]) + h["head"]) * 2 + norms * 4
    assert counts.weight_bytes(c) == want
    assert counts.kv_bytes_per_token(c) == h["kv_tok"]


def test_qwen_total_matches_published_count():
    c, h = cfg("qwen2-1.5b"), HAND["qwen2-1.5b"]
    total = (h["layers"] * (h["layer"] + h["bias"]) + h["head"]
             + (2 * h["layers"] + 1) * h["d"])
    assert total == 1_543_714_304


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode(name):
    c, h = cfg(name), HAND[name]
    per_tok = 2 * (h["layers"] * h["layer"] + h["head"])
    attn = lambda keys: h["layers"] * 4 * h["hq"] * h["dh"] * keys
    lens = [0, 100, 4000]
    assert counts.decode_flops(c, lens) == 3 * per_tok + attn(1 + 101 + 4001)
    assert counts.decode_bytes(c, lens) == (counts.weight_bytes(c)
                                            + (1 + 101 + 4001) * h["kv_tok"])
