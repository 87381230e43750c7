"""Each configuration names its architecture module and its reference, and
the harness finds them by the configuration alone.

The frozen copies below are the harness's own functions as they stood
before the architecture left it (the weight tree, the weights and the
program's ``ModelConfig`` of a dense decoder): the dense module has to give
the same, bit for bit, so that no cell's weights or programs change."""
from __future__ import annotations

import hashlib
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.tests import common

CONFIGS = harness.BENCH / "configs"
TINY = common.DATA / "configs" / "tiny.json"


def frozen_shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // hq
    n = cfg["num_hidden_layers"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"embed": ((v, d), bf), "final_norm/scale": ((d,), f32)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), bf)
    g = "groups/0/"
    out.update({
        g + "mixer_norm/scale": ((n, d), f32),
        g + "attn/wq": ((n, d, hq * dh), bf),
        g + "attn/wk": ((n, d, hkv * dh), bf),
        g + "attn/wv": ((n, d, hkv * dh), bf),
        g + "attn/wo": ((n, hq * dh, d), bf),
        g + "ffn_norm/scale": ((n, d), f32),
        g + "mlp/wi_gate": ((n, d, f), bf),
        g + "mlp/wi_up": ((n, d, f), bf),
        g + "mlp/wo": ((n, f, d), bf),
    })
    if cfg["qkv_bias"]:
        out.update({g + "attn/bq": ((n, hq * dh), bf),
                    g + "attn/bk": ((n, hkv * dh), bf),
                    g + "attn/bv": ((n, hkv * dh), bf)})
    return out


def frozen_make(cfg: dict, seed: int) -> dict:
    spec = frozen_shapes(cfg)

    def nest(flat):
        tree: dict = {}
        for path, leaf in flat.items():
            node = tree
            *head, last = path.split("/")
            for k in head:
                node = node.setdefault(k, {})
            node[last] = leaf
        tree["groups"] = (tree["groups"]["0"],)
        return tree

    def build(key):
        flat = {}
        for i, (path, (shape, dtype)) in enumerate(spec.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            std = 0.02
            if path.endswith(("attn/wq", "attn/wk")):
                std = (2.0 / cfg["hidden_size"]) ** 0.5
            x = 1.0 + 0.1 * z if path.endswith("scale") else std * z
            flat[path] = x.astype(dtype)
        return nest(flat)

    return jax.jit(build)(jax.random.key(seed))


def frozen_model_config(cfg: dict):
    from repro.models.types import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype="bfloat16", source=cfg["source"])


FILES = {"qwen2-1.5b": CONFIGS / "qwen2-1.5b.json",
         "internlm2-1.8b": CONFIGS / "internlm2-1.8b.json", "tiny": TINY}
SMALL = {"tiny": lambda: harness.load_config(TINY),
         "tiny-qwen": lambda: common.QWEN_LIKE,
         "tiny-internlm": lambda: common.INTERNLM_LIKE}


@pytest.mark.parametrize("name", sorted(FILES))
def test_dense_arch_gives_the_frozen_tree_and_program(name):
    cfg = harness.load_config(FILES[name])
    arch = harness.arch(cfg)
    assert list(arch.shapes(cfg).items()) == list(frozen_shapes(cfg).items())
    assert arch.program_config(cfg) == frozen_model_config(cfg)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_weights_are_the_frozen_ones_bit_for_bit(name):
    cfg = SMALL[name]()
    got, want = weights.make(cfg, 2**31 + 5), frozen_make(cfg, 2**31 + 5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("key", harness.MODULE_KEYS)
def test_a_config_without_its_module_is_refused(tmp_path, key):
    cfg = harness.load_config(TINY)
    del cfg[key]
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "tiny.json"
    path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match=re.escape(str(path)) + f".*'{key}'"):
        harness.resolve("tiny.open", common.tiny_benchmark(), tmp_path)
    with pytest.raises(KeyError, match=f"'{key}'"):
        getattr(harness, key)(cfg)       # harness.arch, harness.reference


# A further architecture, in files of its own: the dense decoder with its
# layers in a pattern of two groups, as a model with two kinds of layer has
# them.  Weights, program and reference all follow the pattern.
PAIRED_ARCH = '''
import dataclasses
from bench import harness
BASE = harness.module_at("bench/archs/dense_decoder.py")
PERIOD = 2


def program_config(cfg):
    return dataclasses.replace(BASE.program_config(cfg), period=PERIOD)


def shapes(cfg):
    out = {}
    for path, (shape, dtype) in BASE.shapes(cfg).items():
        if not path.startswith("groups/0/"):
            out[path] = (shape, dtype)
            continue
        for p in range(PERIOD):
            out[f"groups/{p}/" + path[len("groups/0/"):]] = (
                (shape[0] // PERIOD,) + shape[1:], dtype)
    return out


decode_flops = BASE.decode_flops
decode_bytes = BASE.decode_bytes
'''
PAIRED_REFERENCE = '''
import jax
import jax.numpy as jnp
from bench import harness
BASE = harness.module_at("bench/reference/dense_decoder.py")
BLOCK = BASE.BLOCK


def logit_stats(w, tokens, targets, cfg, quant=None):
    # layer g * period + p is groups[p][g]
    layers = jax.tree.map(
        lambda *g: jnp.stack(g, 1).reshape(-1, *g[0].shape[1:]), *w["groups"])
    return BASE.logit_stats({**w, "groups": (layers,)}, tokens, targets, cfg,
                            quant)
'''


def _files(root) -> dict:
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_further_architecture_needs_only_new_files(tmp_path):
    before = _files(harness.BENCH)
    (tmp_path / "paired.py").write_text(PAIRED_ARCH)
    (tmp_path / "paired_ref.py").write_text(PAIRED_REFERENCE)
    for d in ("configs", "traffic", "limits"):
        (tmp_path / d).mkdir()
    cfg = harness.load_config(TINY)
    cfg.update(name="tiny-paired", arch=str(tmp_path / "paired.py"),
               reference=str(tmp_path / "paired_ref.py"))
    (tmp_path / "configs" / "tiny-paired.json").write_text(json.dumps(cfg))
    for src, dst in (("traffic/tiny_open.json", "traffic/tiny_open.json"),
                     ("limits/tiny.open.json", "limits/tiny-paired.open.json")):
        (tmp_path / dst).write_text((common.DATA / src).read_text())
    bench = common.tiny_benchmark()
    bench["workloads"] = [{"name": "tiny-paired.open", "config": "tiny-paired",
                           "traffic": "tiny_open", "chips": 1}]
    seen = {}

    def hook(eng):
        seen.update(period=eng.cfg.period, groups=len(eng.params["groups"]))

    seed = 2**31 + 23
    cell = harness.resolve("tiny-paired.open", bench, tmp_path)
    out = harness.window(cell, seed, 3.0, False, t_start=time.time(),
                         hook=hook)
    line = harness.result(cell, common.CPU, out, seed, False)
    assert seen == {"period": 2, "groups": 2}
    assert line["correct"], line["checks"]
    assert line["checks"]["tokens_compared"]["value"] >= 24
    assert _files(harness.BENCH) == before
