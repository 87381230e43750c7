"""Per-kernel allclose sweeps: Pallas (interpreted on the CPU backend) vs
pure-jnp oracles, across shapes and dtypes, plus hypothesis property tests
on invariants.  tests/test_tpu_compile.py compiles the same kernels for a
TPU v5e."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gather_runahead import ops as gr_ops
from repro.kernels.gather_runahead import ref as gr_ref
from repro.kernels.moe_dispatch import ops as moe_ops
from repro.kernels.moe_dispatch import ref as moe_ref
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention import ref as pa_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref

TOLS = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
        jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# gather_runahead
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("block_rows", [pytest.param(8, id="runahead"),
                                        pytest.param(16, id="runahead16")])
@pytest.mark.parametrize("n,v,d", [(32, 128, 128), (64, 1024, 256)])
def test_gather_matches_ref(block_rows, dtype, n, v, d):
    """Bitwise: every row, odd and even (bf16 rows share 32-bit words)."""
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(v, d)), dtype)
    idx = jnp.asarray(rng.integers(0, v, n), jnp.int32)
    out = gr_ops.gather(table, idx, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gr_ref.gather_ref(table, idx)))


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_gather_runahead_depth_invariance(depth):
    """The runahead window depth (MSHR analogue) must not change results."""
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 256, 64), jnp.int32)
    out = gr_ops.gather(table, idx, impl="runahead", depth=depth)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(gr_ref.gather_ref(table, idx)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), fanin=st.sampled_from([2, 4, 8]))
def test_gather_bag_matches_ref(seed, fanin):
    rng = np.random.default_rng(seed)
    s, v, d = 16, 128, 128
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, v, (s, fanin)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(s, fanin)), jnp.float32)
    out = gr_ops.gather_bag(table, idx, w)
    ref = gr_ref.gather_bag_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fanin", [2, 4])
def test_gather_bag_matches_ref_dtypes(dtype, fanin):
    rng = np.random.default_rng(fanin)
    s, v, d = 16, 256, 128
    table = jnp.asarray(rng.normal(size=(v, d)), dtype)
    idx = jnp.asarray(rng.integers(0, v, (s, fanin)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(s, fanin)), jnp.float32)
    out = gr_ops.gather_bag(table, idx, w)
    ref = gr_ref.gather_bag_ref(table, idx, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96),
                                           (False, None)])
@pytest.mark.parametrize("s,hq,hkv", [(256, 4, 4), (256, 4, 2), (512, 2, 1)])
def test_flash_attention_matches_ref(dtype, causal, window, s, hq, hkv):
    rng = np.random.default_rng(2)
    b, d = 2, 128
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    out = fa_ops.attention(q, k, v, causal=causal, window=window)
    ke = jnp.repeat(k, hq // hkv, axis=1)
    ve = jnp.repeat(v, hq // hkv, axis=1)
    ref = fa_ref.attention_ref(q, ke, ve, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("q_block,kv_block", [(64, 64), (128, 256), (256, 128)])
def test_flash_attention_block_invariance(q_block, kv_block):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.float32)
    out = fa_ops.attention(q, k, v, q_block=q_block, kv_block=kv_block)
    ref = fa_ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_scan_matches_ref(dtype, chunk):
    rng = np.random.default_rng(4)
    b, s, h, p, n = 2, 128, 4, 16, 8
    xh = jnp.asarray(rng.normal(size=(b, s, h, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.4, (b, s, h)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-1, 0.3, (h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), dtype)
    dsk = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    out = ssd_ops.ssd(xh, dt, a_log, bm, cm, dsk, chunk=chunk)
    ref, _ = ssd_ref.ssd_ref(xh.astype(jnp.float32), dt, a_log,
                             bm.astype(jnp.float32), cm.astype(jnp.float32),
                             dsk)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


# ---------------------------------------------------------------------------
# moe_dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_dispatch_matches_ref(dtype):
    rng = np.random.default_rng(5)
    t, d, n_slots = 64, 128, 48
    x = jnp.asarray(rng.normal(size=(t, d)), dtype)
    # unique slots for the kept tokens (capacity semantics), some dropped
    perm = rng.permutation(n_slots)
    slot = np.full(t, -1, np.int32)
    keep = rng.choice(t, size=n_slots, replace=False)
    slot[keep] = perm
    slot = jnp.asarray(slot)
    out = moe_ops.dispatch(x, slot, n_slots=n_slots)
    ref = moe_ref.dispatch_ref(x, slot, n_slots)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), k=st.sampled_from([1, 2, 4]))
def test_moe_combine_matches_ref(seed, k):
    rng = np.random.default_rng(seed)
    t, d, n_slots = 32, 128, 64
    ye = jnp.asarray(rng.normal(size=(n_slots, d)), jnp.float32)
    slot = rng.integers(0, n_slots, (t, k)).astype(np.int32)
    slot[rng.random((t, k)) < 0.2] = -1                   # dropped tokens
    w = jnp.asarray(rng.random((t, k)), jnp.float32)
    out = moe_ops.combine(ye, jnp.asarray(slot), w)
    ref = moe_ref.combine_ref(ye, jnp.asarray(slot), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_combine_roundtrip():
    """combine(dispatch(x)) with k=1, weight 1 recovers kept tokens."""
    rng = np.random.default_rng(9)
    t, d = 32, 128
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    slot = jnp.asarray(rng.permutation(t).astype(np.int32))
    xe = moe_ops.dispatch(x, slot, n_slots=t)
    y = moe_ops.combine(xe, slot[:, None], jnp.ones((t, 1), jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page,pps", [(16, 4), (32, 8)])
def test_paged_attention_matches_ref(dtype, page, pps):
    rng = np.random.default_rng(6)
    b, h, d, pool = 4, 4, 128, 64
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(pool, page, h, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(pool, page, h, d)), dtype)
    pt = jnp.asarray(rng.choice(pool, size=(b, pps), replace=False)
                     if b * pps <= pool else
                     rng.integers(0, pool, (b, pps)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, page * pps + 1, b), jnp.int32)
    out = pa_ops.paged_attention(q, kp, vp, pt, lengths)
    ref = pa_ref.paged_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


def test_paged_attention_matches_dense_decode():
    """Paged KV with an identity page table equals dense decode attention."""
    from repro.models import layers
    rng = np.random.default_rng(7)
    b, h, d, page, pps = 2, 4, 64, 16, 4
    s = page * pps
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    pos = s - 1
    dense = layers.decode_attention(q, kc, vc, jnp.arange(s), pos=pos)
    # lay the same KV into pages: page pool id = b * pps + j
    kp = kc.transpose(0, 2, 1, 3).reshape(b * pps, page, h, d)
    vp = vc.transpose(0, 2, 1, 3).reshape(b * pps, page, h, d)
    pt = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    lengths = jnp.full((b,), pos + 1, jnp.int32)
    paged = pa_ops.paged_attention(q[:, :, 0], kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense[:, :, 0]),
                               rtol=2e-5, atol=2e-5)
