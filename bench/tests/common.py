"""Small configurations and a CPU run of the harness for the tests."""
from __future__ import annotations

import json
import pathlib
import time

from bench import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
WORKLOAD = "tiny.open"

DENSE = dict(arch="bench/archs/dense_decoder.py",
             reference="bench/reference/dense_decoder.py")

# qwen2-like (tied head, q/k/v bias, 6 query heads over 1 kv head) and
# internlm2-like (untied head, no bias, 4 over 2), both tiny
QWEN_LIKE = dict(DENSE, name="tiny-qwen", source="test", hidden_size=96,
                 intermediate_size=160, num_attention_heads=6,
                 num_key_value_heads=1, num_hidden_layers=2, vocab_size=384,
                 rms_norm_eps=1e-6, rope_theta=1e6, tie_word_embeddings=True,
                 qkv_bias=True)
INTERNLM_LIKE = dict(DENSE, name="tiny-internlm", source="test",
                     hidden_size=64, intermediate_size=128,
                     num_attention_heads=4, num_key_value_heads=2,
                     num_hidden_layers=3,
                     vocab_size=320, rms_norm_eps=1e-5, rope_theta=1e6,
                     tie_word_embeddings=False, qkv_bias=False)


# each tiny cell stands in for the chip's cell of its arrivals, metric for
# metric
STANDS_FOR = {"qwen2-1.5b.chat": "tiny.open",
              "internlm2-1.8b.docs": "tiny.closed"}


TINY = ["tiny.open", "tiny.closed"]


def tiny_benchmark() -> dict:
    """BENCHMARK.json with its metrics pointed at the tiny CPU cells."""
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            m["workloads"] = ([STANDS_FOR[w] for w in m["workloads"]]
                              if "workloads" in m else TINY)
    bench["workloads"] = [
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1},
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny_closed",
         "chips": 1}]
    return bench


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def run_tiny(seed: int, seconds: float = 3.0, trace: bool = False,
             hook=None, workload: str = WORKLOAD) -> dict:
    """One run of a tiny cell on the CPU, past the look for a chip."""
    cell = harness.resolve(workload, tiny_benchmark(), DATA)
    out = harness.window(cell, seed, seconds, trace, t_start=time.time(),
                         hook=hook)
    return harness.result(cell, CPU, out, seed, trace)
