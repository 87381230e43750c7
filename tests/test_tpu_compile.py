"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

No chip is needed: the TPU compiler is installed with JAX and compiles for a
described ``v5e:2x2`` topology.  This catches what the CPU interpreter
cannot (tile-misaligned DMA slices, block shapes off the (8, 128) rule, ops
Mosaic cannot lower).  Nothing runs, so results are checked elsewhere
(tests/test_kernels.py here, ``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.gather_runahead import gather_runahead as gr
from repro.kernels.paged_attention import paged_attention as pa

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
