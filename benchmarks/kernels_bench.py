"""Pallas kernel microbenchmarks (interpreted on the CPU backend).

CPU interpreter timings are NOT TPU performance; the value of these rows is
(a) exercising every kernel end-to-end from the benchmark harness and
(b) reporting the kernels' modeled HBM traffic (the quantity the runahead
design optimizes).  TPU wall-time belongs to real-hardware runs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.gather_runahead import ops as gr_ops
from repro.kernels.moe_dispatch import ops as moe_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.ssd_scan import ops as ssd_ops


def _timeit(fn, *args, n=3, **kw):
    fn(*args, **kw)                      # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run() -> None:
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(4096, 256)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 4096, 256), jnp.int32)
    for depth in (1, 2, 4):
        us = _timeit(gr_ops.gather, table, idx, impl="runahead", depth=depth)
        bytes_moved = idx.size * table.shape[1] * 4
        print(f"kernel/gather_runahead/depth_{depth},{us:.0f},"
              f"hbm_bytes={bytes_moved}", flush=True)

    q = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 512, 128)), jnp.float32)
    us = _timeit(fa_ops.attention, q, k, k)
    flash_bytes = 4 * q.size * 4
    print(f"kernel/flash_attention/512,{us:.0f},hbm_bytes={flash_bytes};"
          f"scores_stay_in_vmem=1", flush=True)

    xh = jnp.asarray(rng.normal(size=(2, 256, 8, 16)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.4, (2, 256, 8)), jnp.float32)
    a_log = jnp.zeros((8,), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(2, 256, 16)), jnp.float32)
    dsk = jnp.ones((8,), jnp.float32)
    us = _timeit(ssd_ops.ssd, xh, dt, a_log, bm, bm, dsk, chunk=64)
    print(f"kernel/ssd_scan/256,{us:.0f},state_stays_in_vmem=1", flush=True)

    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    slot = jnp.asarray(rng.permutation(128).astype(np.int32))
    us = _timeit(moe_ops.dispatch, x, slot, n_slots=128)
    print(f"kernel/moe_dispatch/128,{us:.0f},", flush=True)

    qd = jnp.asarray(rng.normal(size=(4, 4, 128)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(64, 16, 4, 128)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, 64, (4, 8)), jnp.int32)
    ln = jnp.full((4,), 100, jnp.int32)
    us = _timeit(pa_ops.paged_attention, qd, kp, kp, pt, ln)
    print(f"kernel/paged_attention/8pages,{us:.0f},", flush=True)
