"""Mamba-2 SSD chunked scan, Pallas TPU.

Grid ``(B, num_chunks)`` with the chunk dimension innermost and the SSD state
``[H, P, N]`` carried in VMEM scratch across chunk steps (initialized at
chunk 0).  Each step runs the matmul-form intra-chunk block (MXU) plus the
rank-1 state update — the inter-chunk recurrence never leaves VMEM, which is
the kernel's point: the HBM traffic is exactly x/dt/B/C in and y out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, alog_ref, dskip_ref,
                y_ref, state_sc, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0].astype(jnp.float32)       # [Q, H, P]
    dt = dt_ref[0].astype(jnp.float32)     # [Q, H]
    la = la_ref[0].astype(jnp.float32)     # [Q, H]
    bm = b_ref[0].astype(jnp.float32)      # [Q, N]
    cm = c_ref[0].astype(jnp.float32)      # [Q, N]
    d_skip = dskip_ref[...].astype(jnp.float32)  # [H]

    cum = jnp.cumsum(la, axis=0)           # [Q, H]
    total = cum[-1, :]                     # [H]

    # intra-chunk: att[i,j,h] = (C_i . B_j) * exp(cum_i - cum_j) * causal
    seg = cum[:, None, :] - cum[None, :, :]              # [Qi, Qj, H]
    causal = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(causal[..., None], jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores[..., None] * decay                      # [Qi, Qj, H]
    xdt = x * dt[..., None]                              # [Q, H, P]
    y_intra = jnp.einsum("ijh,jhp->ihp", att, xdt)

    # inter-chunk: y_inter[i] = exp(cum_i) * (C_i . S_prev)
    s_prev = state_sc[...]                               # [H, P, N]
    y_inter = jnp.einsum("in,hpn->ihp", cm, s_prev) * jnp.exp(cum)[..., None]

    # state update
    w_in = jnp.exp(total[None, :] - cum) * dt            # [Q, H]
    s_new = s_prev * jnp.exp(total)[:, None, None] + jnp.einsum(
        "jn,jh,jhp->hpn", bm, w_in, x)
    state_sc[...] = s_new

    y = y_intra + y_inter + d_skip[None, :, None] * x
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(xh, dt, a_log, b_mat, c_mat, d_skip, *, chunk: int = 64,
             interpret: bool):
    """xh: [B,S,H,P]; dt: [B,S,H]; b/c: [B,S,N]; returns y [B,S,H,P]."""
    bsz, s, h, p = xh.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    la = dt * (-jnp.exp(a_log))

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, h), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, h), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((h,), lambda b, c: (0,)),
            pl.BlockSpec((h,), lambda b, c: (0,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, h, p), lambda b, c: (b, c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bsz, s, h, p), xh.dtype),
        interpret=interpret,
    )(xh, dt, la, b_mat, c_mat, a_log, d_skip)
