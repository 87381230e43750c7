"""Runahead row-gather Pallas TPU kernels.

TPU adaptation of the paper's runahead mechanism: the index stream is known
ahead of compute ("valid memory requests"), so future rows are prefetched
HBM->VMEM while the current block computes.

* :func:`runahead_gather` — *explicit* multi-buffered DMA: ``depth`` VMEM
  slots hold in-flight row fetches (``depth`` = the MSHR-entry analogue,
  §3.4.1/Fig. 14); the kernel issues ``make_async_copy`` for block ``i +
  depth`` before computing block ``i``.  The table lives in ``pl.ANY``
  (compiler-chosen, HBM at size) and only the gathered rows ever enter VMEM.
* :func:`gather_bag` — the full Listing-1 aggregation (padded-CSR GCN
  ``aggregate`` / embedding-bag): per output row, ``K`` irregular row
  fetches are combined with edge weights in VMEM.

Row fetches: Mosaic moves HBM data in whole layout tiles, and a table row
is a slice of an 8-row tile (for bf16, of row pairs packed into 32-bit
words).  The kernels therefore view the table as 32-bit words, fetch the
aligned 8-row group that holds each wanted row, and pick the row out in
VMEM with bit-exact selects: 8x the bytes of the rows themselves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP = 8       # table rows per fetched tile group


def _pack(dtype) -> int:
    """Table rows per 32-bit word: 1 (32-bit dtypes) or 2 (bfloat16)."""
    dtype = jnp.dtype(dtype)
    assert dtype.itemsize == 4 or dtype == jnp.bfloat16, \
        f"unsupported table dtype {dtype}"
    return 4 // dtype.itemsize


def _check_table(table: jax.Array) -> None:
    _pack(table.dtype)
    assert table.shape[0] % GROUP == 0, \
        f"table rows must be a multiple of {GROUP}: {table.shape}"


def _group_copy(table_ref, row, dst, sem):
    """DMA descriptor: the word rows of the 8-row group holding table row
    ``row`` (HBM) -> ``dst`` (VMEM ``[GROUP // pack, D]`` uint32)."""
    words = GROUP // _pack(table_ref.dtype)
    start = pl.multiple_of((row // GROUP) * words, words)
    return pltpu.make_async_copy(
        table_ref.bitcast(jnp.uint32).at[pl.ds(start, words)], dst, sem)


def _pick_row(group_ref, row, dtype):
    """Table row ``row`` out of its fetched group ``group_ref`` (VMEM words
    ``[w, D]``) as ``[1, D]`` of ``dtype`` (bf16 rows widen exactly to
    float32)."""
    pack = _pack(dtype)
    word = group_ref[pl.ds((row % GROUP) // pack, 1), :]
    if pack == 1:
        return pltpu.bitcast(word, dtype)
    # row 2k sits in the low half of its word, 2k+1 in the high half; a
    # bf16 value is the high half of the float32 with the same bits
    shift = (row % 2).astype(jnp.uint32) * 16
    bits = jax.lax.shift_left(jax.lax.shift_right_logical(word, shift),
                              jnp.uint32(16))
    return pltpu.bitcast(bits, jnp.float32)


def _scratch(depth: int, lead: tuple, d: int, dtype):
    return pltpu.VMEM((depth, *lead, GROUP // _pack(dtype), d), jnp.uint32)


# ---------------------------------------------------------------------------
# explicit runahead (manual multi-buffered DMA)
# ---------------------------------------------------------------------------

def _runahead_kernel(idx_ref, table_ref, o_ref, scratch, sems, *,
                     block_rows: int, depth: int, n_blocks: int):
    i = pl.program_id(0)

    def block_copies(b, slot):
        """The ``block_rows`` group DMAs of index-block ``b`` into ``slot``."""
        return [_group_copy(table_ref, idx_ref[b * block_rows + r],
                            scratch.at[slot, r], sems.at[slot, r])
                for r in range(block_rows)]

    # prologue: fill the runahead window (blocks 0..depth-1)
    @pl.when(i == 0)
    def _():
        for k in range(min(depth, n_blocks)):
            for c in block_copies(k, k % depth):
                c.start()

    slot = i % depth
    for c in block_copies(i, slot):
        c.wait()
    sel = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    out = None
    for r in range(block_rows):
        row = _pick_row(scratch.at[slot, r], idx_ref[i * block_rows + r],
                        o_ref.dtype)
        out = row if out is None else jnp.where(sel == r, row, out)
    o_ref[...] = out.astype(o_ref.dtype)

    # runahead: prefetch block i+depth now that slot is free
    @pl.when(i + depth < n_blocks)
    def _():
        for c in block_copies(i + depth, slot):
            c.start()


def runahead_gather(table: jax.Array, idx: jax.Array, *, block_rows: int = 8,
                    depth: int = 2, interpret: bool) -> jax.Array:
    _check_table(table)
    n = idx.shape[0]
    d = table.shape[1]
    assert n % block_rows == 0, (n, block_rows)
    n_blocks = n // block_rows
    depth = min(depth, n_blocks)
    kernel = functools.partial(_runahead_kernel, block_rows=block_rows,
                               depth=depth, n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, d),
                               lambda i, idx_ref: (i, 0)),
        scratch_shapes=[
            _scratch(depth, (block_rows,), d, table.dtype),
            pltpu.SemaphoreType.DMA((depth, block_rows)),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), table.dtype),
        interpret=interpret,
    )(idx, table)


# ---------------------------------------------------------------------------
# gather-bag (Listing 1: weighted aggregation of K irregular rows per output)
# ---------------------------------------------------------------------------

def _bag_kernel(idx_ref, w_ref, table_ref, o_ref, scratch, sems, *,
                block_rows: int, fanin: int, depth: int, n_blocks: int):
    i = pl.program_id(0)

    def block_copies(b, slot):
        return [_group_copy(table_ref,
                            idx_ref[(b * block_rows + r) * fanin + k],
                            scratch.at[slot, r, k], sems.at[slot])
                for r in range(block_rows) for k in range(fanin)]

    @pl.when(i == 0)
    def _():
        for j in range(min(depth, n_blocks)):
            for c in block_copies(j, j % depth):
                c.start()

    slot = i % depth
    for c in block_copies(i, slot):
        c.wait()
    sel = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    out = None
    for r in range(block_rows):
        s = i * block_rows + r
        acc = None
        for k in range(fanin):
            row = _pick_row(scratch.at[slot, r, k], idx_ref[s * fanin + k],
                            o_ref.dtype)
            term = w_ref[s * fanin + k] * row.astype(jnp.float32)
            acc = term if acc is None else acc + term            # [1, D]
        out = acc if out is None else jnp.where(sel == r, acc, out)
    o_ref[...] = out.astype(o_ref.dtype)

    @pl.when(i + depth < n_blocks)
    def _():
        for c in block_copies(i + depth, slot):
            c.start()


def gather_bag(table: jax.Array, idx: jax.Array, weights: jax.Array, *,
               depth: int = 2, interpret: bool) -> jax.Array:
    """out[s] = sum_k w[s,k] * table[idx[s,k]], accumulated in float32 from
    weights rounded to the table dtype (the :func:`ref.gather_bag_ref`
    semantics)."""
    _check_table(table)
    n, fanin = idx.shape
    d = table.shape[1]
    block_rows = GROUP          # output rows per grid step: one (8, 128) tile
    assert n % block_rows == 0, (n, block_rows)
    n_blocks = n // block_rows
    depth = min(depth, n_blocks)
    # flat scalar-prefetch operands: SMEM pads a 2-D array's minor dim
    weights = weights.astype(table.dtype).astype(jnp.float32).reshape(-1)
    idx = idx.reshape(-1)
    kernel = functools.partial(_bag_kernel, block_rows=block_rows,
                               fanin=fanin, depth=depth, n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # idx and weights, [S*K] each
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, d),
                               lambda i, i_ref, w_ref: (i, 0)),
        scratch_shapes=[
            _scratch(depth, (block_rows, fanin), d, table.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), table.dtype),
        interpret=interpret,
    )(idx, weights, table)
