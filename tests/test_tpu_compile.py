"""The main path's Pallas kernels compile for a TPU v5e, at real widths,
and the serve programs update their page pool in place there.

No chip is needed: the TPU compiler is installed with JAX and compiles for a
described ``v5e:2x2`` topology.  This catches what the CPU interpreter
cannot (tile-misaligned DMA slices, block shapes off the (8, 128) rule, ops
Mosaic cannot lower).  Nothing runs, so results are checked elsewhere
(tests/test_kernels.py here, ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.gather_runahead import gather_runahead as gr
from repro.kernels.paged_attention import paged_attention as pa
from repro.launch.steps import build_serve_engine_steps
from repro.models import api

QWEN = registry.get("qwen2-1.5b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's executables are written to the persistent cache
    # but cannot be read back without the chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


TABLES = {
    "f32-128": ((4096, 128), jnp.float32),
    "bf16-embed": ((QWEN.vocab_size, QWEN.d_model), jnp.bfloat16),
}


@pytest.mark.parametrize("table", sorted(TABLES))
def test_runahead_gather_compiles(one_chip, table):
    shape, dtype = TABLES[table]
    hlo = _compiled_hlo(
        lambda t, i: gr.runahead_gather(t, i, depth=4, interpret=False),
        _spec(one_chip, shape, dtype), _spec(one_chip, (65536,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("table", sorted(TABLES))
def test_gather_bag_compiles(one_chip, table):
    shape, dtype = TABLES[table]
    hlo = _compiled_hlo(
        lambda t, i, w: gr.gather_bag(t, i, w, interpret=False),
        _spec(one_chip, shape, dtype), _spec(one_chip, (4096, 16), jnp.int32),
        _spec(one_chip, (4096, 16), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_paged_attention_compiles_at_qwen2_decode(one_chip):
    """The serve path's kernel read (``attn_read="kernel"``): 8 slots of
    qwen2-1.5b heads (KV repeated to the 12 query heads), 16-token pages,
    2048-token slots."""
    b, h, d, page, pps = 8, QWEN.n_heads, QWEN.d_head, 16, 2048 // 16
    pool = (1 + b * pps, page, h, d)
    hlo = _compiled_hlo(
        lambda q, k, v, pt, ln: pa.paged_attention(q, k, v, pt, ln,
                                                   interpret=False),
        _spec(one_chip, (b, h, d), jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, (b, pps), jnp.int32), _spec(one_chip, (b,), jnp.int32))
    assert "tpu_custom_call" in hlo


# qwen2-1.5b's widths, 3 layers: each stacked K or V pool is 47 MiB, too
# large for on-chip memory, so a second stack would count as temporaries
SERVE = dict(groups=3, slots=16, max_len=2048, chunk=256)


def _result_type_and_opcode(rest):
    """Split an HLO instruction's right-hand side into (type, opcode)."""
    if rest.startswith("("):                      # a tuple: balanced parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:]
    else:
        typ, _, rest = rest.partition(" ")
    return typ, rest.strip().partition("(")[0]


MOVES = ("dynamic-slice", "dynamic-update-slice", "copy", "copy-start",
         "copy-done")


def _pool_traffic(hlo, stack, layer):
    """Instructions that slice, write back, copy or allocate a pool:
    a dynamic-slice, dynamic-update-slice or copy (by opcode or by the
    fusion named for one) whose result is one layer's pool or the stacked
    pool, and any buffer allocated at the stacked pool's shape."""
    shapes = (f"bf16[{stack}]", f"bf16[{layer}]")
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)", line)
        if not m:
            continue
        name, (typ, opcode) = m.group(1), _result_type_and_opcode(m.group(2))
        if not any(sh in typ for sh in shapes):
            continue
        moves = (opcode in MOVES
                 or re.search(r"dynamic[-_](update[-_])?slice", name))
        allocates = "AllocateBuffer" in line and shapes[0] in typ
        if moves or allocates:
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def serve_programs(one_chip):
    cfg = dataclasses.replace(QWEN, n_layers=SERVE["groups"])
    steps = build_serve_engine_steps(cfg, slots=SERVE["slots"],
                                     max_len=SERVE["max_len"])
    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    def arg(shape, dtype):
        return _spec(one_chip, shape, dtype)

    params = on_chip(api.abstract_params(cfg))
    cache = on_chip(steps.cache_abs)
    b, c = SERVE["slots"], SERVE["chunk"]
    args = {
        "decode": (params, arg((b,), jnp.int32), arg((b,), jnp.bool_),
                   arg((b,), jnp.float32), arg((b, 2), jnp.uint32), cache),
        "prefill": (params, arg((c,), jnp.int32), arg((), jnp.int32),
                    arg((), jnp.int32), arg((), jnp.float32),
                    arg((2,), jnp.uint32), cache),
    }
    pool = steps.cache_abs["layers"][0]["k_pages"]
    return pool, {name: getattr(steps, name).lower(*a).compile()
                  for name, a in args.items()}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serve_program_updates_pool_in_place(serve_programs, program):
    """The layer scan carries the stacked pool: no layer's pool is sliced
    out or written back, no second stack is allocated or copied back, and
    the program's temporaries stay under one stacked pool."""
    pool, compiled = serve_programs
    stack = ",".join(map(str, pool.shape))
    layer = ",".join(map(str, pool.shape[1:]))
    exe = compiled[program]
    assert _pool_traffic(exe.as_text(), stack, layer) == []
    pool_bytes = pool.size * pool.dtype.itemsize
    assert exe.memory_analysis().temp_size_in_bytes < pool_bytes
