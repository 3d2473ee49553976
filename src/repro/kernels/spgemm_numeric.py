"""Pallas kernel: row-wise numeric SpGEMM accumulation (TPU-native).

Per grid step (a block of output rows): gather the intermediate products
(columns AND value-products) into a static (BS, F2) buffer, bitonic-sort the
key/value pairs, then compute per-run value sums with the log-step segmented
scan.  The kernel emits the *uncompacted* sorted buffer: sorted columns, a
first-of-run mask, and run-sums placed at each run's first slot.

The O(F log F) sort + O(F log F) segmented scan stays in the kernel; the
compaction into the predicted-capacity CSR buffers is an XLA keyed sort
outside (``compact`` → ``repro.core.spgemm.place_sorted``).  This split keeps
the kernel free of VMEM scatters while the MXU-unfriendly memory traffic is
still one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.csr import COL_SENTINEL, pad_row_ids
from .sortnet import (bitonic_sort_pairs, segmented_run_sums, next_pow2,
                      pad_to_pow2)


def _kernel(rows_ref, a_rpt_ref, a_col_ref, a_val_ref, b_rpt_ref, b_col_ref,
            b_val_ref, rownnz_b_ref, col_out_ref, val_out_ref, first_out_ref,
            *, block_rows: int, max_deg_a: int, max_deg_b: int):
    rows = rows_ref[...]
    deg_a = a_rpt_ref[rows + 1] - a_rpt_ref[rows]
    ia = jax.lax.broadcasted_iota(jnp.int32, (block_rows, max_deg_a), 1)
    idx_a = jnp.clip(a_rpt_ref[rows][:, None] + ia, 0, a_col_ref.shape[0] - 1)
    valid_a = ia < deg_a[:, None]
    ks = jnp.where(valid_a, a_col_ref[idx_a], 0)
    av = jnp.where(valid_a, a_val_ref[idx_a], 0.0)

    deg_b = jnp.where(valid_a, rownnz_b_ref[ks], 0)
    ib = jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, max_deg_a, max_deg_b), 2)
    idx_b = jnp.clip(b_rpt_ref[ks][:, :, None] + ib, 0, b_col_ref.shape[0] - 1)
    valid = valid_a[:, :, None] & (ib < deg_b[:, :, None])
    cols = jnp.where(valid, b_col_ref[idx_b], COL_SENTINEL)
    vals = jnp.where(valid, av[:, :, None] * b_val_ref[idx_b], 0.0)

    f = max_deg_a * max_deg_b
    cbuf, vbuf = pad_to_pow2(cols.reshape(block_rows, f),
                             vals.reshape(block_rows, f), COL_SENTINEL)
    c_s, v_s = bitonic_sort_pairs(cbuf, vbuf)
    first, run_sums = segmented_run_sums(c_s, v_s, COL_SENTINEL)
    col_out_ref[...] = c_s
    val_out_ref[...] = jnp.where(first, run_sums, 0.0)
    first_out_ref[...] = first.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "block_rows", "interpret"))
def spgemm_numeric_pallas(a_rpt, a_col, a_val, b_rpt, b_col, b_val, rows, *,
                          max_deg_a: int, max_deg_b: int, block_rows: int = 8,
                          interpret: bool, rownnz_b=None):
    """Sorted/run-summed products for ``rows``.

    Returns (sorted_cols (R, F2), run_sums_at_first (R, F2), first_mask (R, F2)).
    ``rownnz_b`` (= ``jnp.diff(b_rpt)``) may be passed in so bucket-iterated
    callers hoist the diff out of their per-bucket calls.
    """
    r = rows.shape[0]
    nblocks = -(-r // block_rows)
    pad_r = nblocks * block_rows
    rows_p = pad_row_ids(rows, block_rows)
    if rownnz_b is None:
        rownnz_b = jnp.diff(b_rpt)
    f2 = next_pow2(max_deg_a * max_deg_b)
    cols, vals, first = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((block_rows, f2), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, f2), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, f2), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((pad_r, f2), jnp.int32),
                   jax.ShapeDtypeStruct((pad_r, f2), jnp.float32),
                   jax.ShapeDtypeStruct((pad_r, f2), jnp.int32)],
        interpret=interpret,
    )(rows_p, a_rpt, a_col, a_val, b_rpt, b_col, b_val, rownnz_b)
    return cols[:r], vals[:r], first[:r]


def compact(cols, vals, first, row_capacity: int):
    """XLA-side compaction into predicted-capacity buffers: the run heads
    are placed by one keyed sort (``core.spgemm.place_sorted``), no scatter.
    ``vals`` is already 0 off the run heads (the kernel writes it so)."""
    from repro.core.spgemm import place_sorted
    first = first.astype(bool)
    out_col, out_val = place_sorted(jnp.where(first, cols, COL_SENTINEL),
                                    vals, row_capacity)
    row_nnz = first.sum(axis=-1, dtype=jnp.int32)
    overflow = jnp.maximum(row_nnz - row_capacity, 0).sum()
    return out_col, out_val, row_nnz, overflow
