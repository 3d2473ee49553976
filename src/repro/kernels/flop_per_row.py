"""Pallas kernel: Algorithm 1 — FLOP per output row.

Grid: one step per block of ``block_rows`` output rows.  The CSR index arrays
(A.rpt, A.col, B row-nnz) are VMEM-resident (no blocking — they are small for
the sampled workloads this feeds; a production variant adds a second grid dim
streaming A.col).  The per-block work is a contiguous dynamic slice of A.rpt,
a 2-D gather from A.col, a gather of B row-nnz and a lane reduction — MXU-free
pure VPU, hardware-aligned when block_rows % 8 == 0 and max_deg_a % 128 == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.csr import pad_row_ids


def _kernel(rpt_ref, col_ref, rownnz_b_ref, out_ref, *, block_rows: int,
            max_deg_a: int):
    i = pl.program_id(0)
    row0 = i * block_rows
    starts = rpt_ref[pl.ds(row0, block_rows)]
    ends = rpt_ref[pl.ds(row0 + 1, block_rows)]
    deg = ends - starts                                         # (BR,)
    ia = jax.lax.broadcasted_iota(jnp.int32, (block_rows, max_deg_a), 1)
    idx = starts[:, None] + ia                                  # (BR, DA)
    valid = ia < deg[:, None]
    cap = col_ref.shape[0]
    cols = col_ref[jnp.clip(idx, 0, cap - 1)]                   # VMEM gather
    b_nnz = rownnz_b_ref[jnp.clip(cols, 0, rownnz_b_ref.shape[0] - 1)]
    contrib = jnp.where(valid, b_nnz, 0)
    out_ref[...] = jnp.sum(contrib, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "max_deg_a", "interpret"))
def flop_per_row_pallas(rpt: jax.Array, col: jax.Array, rownnz_b: jax.Array,
                        *, block_rows: int = 256, max_deg_a: int = 128,
                        interpret: bool) -> jax.Array:
    """floprC for all M rows.  ``rpt`` int32 (M+1,), ``col`` int32 (cap,)."""
    m = rpt.shape[0] - 1
    nblocks = -(-m // block_rows)
    pad_m = nblocks * block_rows
    # pad rpt so every block's [row0, row0+BR] slice is in range; padded rows
    # have deg 0 (rpt repeats its last entry).
    rpt_p = jnp.concatenate(
        [rpt, jnp.broadcast_to(rpt[-1:], (pad_m + 1 - rpt.shape[0],))])
    out = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows, max_deg_a=max_deg_a),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # rpt: full, VMEM
            pl.BlockSpec(memory_space=pl.ANY),   # col: full, VMEM
            pl.BlockSpec(memory_space=pl.ANY),   # rownnz_b: full, VMEM
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((pad_m,), jnp.int32),
        interpret=interpret,
    )(rpt_p, col, rownnz_b)
    return out[:m]


def _rows_kernel(rows_ref, rpt_ref, col_ref, rownnz_b_ref, out_ref, *,
                 block_rows: int, max_deg_a: int):
    """Same reduction, but over an explicit row-id list (a degree bucket)."""
    rows = rows_ref[...]                                        # (BR,)
    starts = rpt_ref[rows]
    deg = rpt_ref[rows + 1] - starts
    ia = jax.lax.broadcasted_iota(jnp.int32, (block_rows, max_deg_a), 1)
    idx = jnp.clip(starts[:, None] + ia, 0, col_ref.shape[0] - 1)
    valid = ia < deg[:, None]
    cols = col_ref[idx]
    b_nnz = rownnz_b_ref[jnp.clip(cols, 0, rownnz_b_ref.shape[0] - 1)]
    out_ref[...] = jnp.sum(jnp.where(valid, b_nnz, 0), axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "max_deg_a", "interpret"))
def flop_rows_pallas(rpt: jax.Array, col: jax.Array, rownnz_b: jax.Array,
                     rows: jax.Array, *, block_rows: int = 256,
                     max_deg_a: int = 128, interpret: bool) -> jax.Array:
    """floprC for the listed ``rows`` only — the binned-pipeline variant,
    sized by the bucket's degree bound instead of the global one."""
    r = rows.shape[0]
    nblocks = -(-r // block_rows)
    pad_r = nblocks * block_rows
    rows_p = pad_row_ids(rows, block_rows)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, block_rows=block_rows,
                          max_deg_a=max_deg_a),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),  # rows: blocked
            pl.BlockSpec(memory_space=pl.ANY),            # rpt
            pl.BlockSpec(memory_space=pl.ANY),            # col
            pl.BlockSpec(memory_space=pl.ANY),            # rownnz_b
        ],
        out_specs=pl.BlockSpec((block_rows,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((pad_r,), jnp.int32),
        interpret=interpret,
    )(rows_p, rpt, col, rownnz_b)
    return out[:r]
