"""Pallas kernel: the sampled symbolic phase of Algorithm 2 (TPU-native).

Per grid step: a block of sampled A rows.  The kernel

  1. gathers each sampled row's A columns (≤ DA) from VMEM,
  2. gathers every referenced B row's columns (≤ DB) — the intermediate
     product columns, a (BS, DA·DB→F2) buffer padded with COL_SENTINEL,
  3. bitonic-sorts the buffer along lanes (static network, DESIGN §3),
  4. counts strict ascents = exact distinct columns z*, and valid slots = f*.

Outputs per-block (z, f) partials; the tiny final reduction happens in XLA.
This is the hash-table replacement: identical result, zero data-dependent
control flow.  VMEM budget: BS·F2·4 bytes for the buffer (+ CSR arrays);
callers pick BS so that BS·F2 ≤ ~1M lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.csr import COL_SENTINEL, pad_row_ids
from .sortnet import bitonic_sort, pad_to_pow2


def _kernel(rows_ref, a_rpt_ref, a_col_ref, b_rpt_ref, b_col_ref,
            rownnz_b_ref, z_ref, f_ref, *, block_samples: int,
            max_deg_a: int, max_deg_b: int, n_valid: int):
    rows = rows_ref[...]                                        # (BS,)
    i = pl.program_id(0)
    pos = i * block_samples + jax.lax.broadcasted_iota(
        jnp.int32, (block_samples,), 0)
    row_ok = pos < n_valid            # block-padding rows contribute nothing
    deg_a = a_rpt_ref[rows + 1] - a_rpt_ref[rows]
    ia = jax.lax.broadcasted_iota(jnp.int32, (block_samples, max_deg_a), 1)
    idx_a = jnp.clip(a_rpt_ref[rows][:, None] + ia, 0, a_col_ref.shape[0] - 1)
    valid_a = row_ok[:, None] & (ia < deg_a[:, None])
    ks = jnp.where(valid_a, a_col_ref[idx_a], 0)                # (BS, DA)

    deg_b = jnp.where(valid_a, rownnz_b_ref[ks], 0)
    ib = jax.lax.broadcasted_iota(
        jnp.int32, (block_samples, max_deg_a, max_deg_b), 2)
    idx_b = jnp.clip(b_rpt_ref[ks][:, :, None] + ib, 0, b_col_ref.shape[0] - 1)
    valid = valid_a[:, :, None] & (ib < deg_b[:, :, None])
    cols = jnp.where(valid, b_col_ref[idx_b], COL_SENTINEL)

    buf, _ = pad_to_pow2(cols.reshape(block_samples, -1), None, COL_SENTINEL)
    srt = bitonic_sort(buf)
    first = (srt[:, :1] != COL_SENTINEL).astype(jnp.int32)
    ascents = ((srt[:, 1:] != srt[:, :-1]) &
               (srt[:, 1:] != COL_SENTINEL)).astype(jnp.int32)
    z_ref[...] = (first[:, 0] + ascents.sum(axis=-1)).sum(keepdims=True)
    f_ref[...] = valid.astype(jnp.int32).reshape(block_samples, -1).sum(
        axis=-1).sum(keepdims=True)


def _fused_kernel(rows_ref, a_rpt_ref, a_col_ref, b_rpt_ref, b_col_ref,
                  rownnz_b_ref, z_ref, f_ref, flop_ref, *, block_samples: int,
                  max_deg_a: int, max_deg_b: int, n_valid: int):
    """Fused Algorithm 1 + Algorithm 2 body for one block of sampled rows.

    The A-row gather (``ks``/``valid_a``) and the B-degree lookup are shared:
    FLOP-per-sampled-row is a lane reduction over ``deg_b`` while the same
    ``deg_b`` drives the product-column expansion that the bitonic distinct
    count consumes.  Rows at positions ≥ ``n_valid`` are block padding and
    contribute nothing (no duplicate-correction pass needed).
    """
    i = pl.program_id(0)
    pos = i * block_samples + jax.lax.broadcasted_iota(
        jnp.int32, (block_samples,), 0)
    row_ok = pos < n_valid                                      # (BS,)
    rows = rows_ref[...]
    deg_a = a_rpt_ref[rows + 1] - a_rpt_ref[rows]
    ia = jax.lax.broadcasted_iota(jnp.int32, (block_samples, max_deg_a), 1)
    idx_a = jnp.clip(a_rpt_ref[rows][:, None] + ia, 0, a_col_ref.shape[0] - 1)
    valid_a = row_ok[:, None] & (ia < deg_a[:, None])
    ks = jnp.where(valid_a, a_col_ref[idx_a], 0)                # (BS, DA)

    deg_b = jnp.where(valid_a, rownnz_b_ref[ks], 0)
    flop = deg_b.sum(axis=1).astype(jnp.int32)                  # (BS,)

    ib = jax.lax.broadcasted_iota(
        jnp.int32, (block_samples, max_deg_a, max_deg_b), 2)
    idx_b = jnp.clip(b_rpt_ref[ks][:, :, None] + ib, 0, b_col_ref.shape[0] - 1)
    valid = valid_a[:, :, None] & (ib < deg_b[:, :, None])
    cols = jnp.where(valid, b_col_ref[idx_b], COL_SENTINEL)

    buf, _ = pad_to_pow2(cols.reshape(block_samples, -1), None, COL_SENTINEL)
    srt = bitonic_sort(buf)
    first = (srt[:, :1] != COL_SENTINEL).astype(jnp.int32)
    ascents = ((srt[:, 1:] != srt[:, :-1]) &
               (srt[:, 1:] != COL_SENTINEL)).astype(jnp.int32)
    z_ref[...] = (first[:, 0] + ascents.sum(axis=-1)).sum(keepdims=True)
    f_ref[...] = flop.sum(keepdims=True)
    flop_ref[...] = flop


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "block_samples", "interpret"))
def fused_flop_symbolic_pallas(a_rpt, a_col, b_rpt, b_col, rows, *,
                               max_deg_a: int, max_deg_b: int,
                               block_samples: int = 8, interpret: bool):
    """One pallas_call → (z*, f*, flop-per-sampled-row (S,)).

    The binned predictor issues this once per bucket: the sampled symbolic
    pass and the sampled rows' FLOP share a single A-row gather instead of
    the two separate kernel sweeps of the unfused path.
    """
    s = rows.shape[0]
    nblocks = -(-s // block_samples)
    pad_s = nblocks * block_samples
    rows_p = pad_row_ids(rows, block_samples)  # masked in-kernel via n_valid
    rownnz_b = jnp.diff(b_rpt)
    z_b, f_b, flop = pl.pallas_call(
        functools.partial(_fused_kernel, block_samples=block_samples,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                          n_valid=s),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_samples,), lambda i: (i,)),  # rows: blocked
            pl.BlockSpec(memory_space=pl.ANY),               # a_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # a_col
            pl.BlockSpec(memory_space=pl.ANY),               # b_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # b_col
            pl.BlockSpec(memory_space=pl.ANY),               # rownnz_b
        ],
        out_specs=[pl.BlockSpec((1,), lambda i: (i,)),
                   pl.BlockSpec((1,), lambda i: (i,)),
                   pl.BlockSpec((block_samples,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((nblocks,), jnp.int32),
                   jax.ShapeDtypeStruct((nblocks,), jnp.int32),
                   jax.ShapeDtypeStruct((pad_s,), jnp.int32)],
        interpret=interpret,
    )(rows_p, a_rpt, a_col, b_rpt, b_col, rownnz_b)
    return z_b.sum(), f_b.sum(), flop[:s]


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "block_samples", "interpret"))
def sampled_symbolic_pallas(a_rpt, a_col, b_rpt, b_col, rows, *,
                            max_deg_a: int, max_deg_b: int,
                            block_samples: int = 8, interpret: bool):
    """Returns (z*, f*) — exact sampled NNZ and sampled FLOP (int32 scalars)."""
    s = rows.shape[0]
    nblocks = -(-s // block_samples)
    rows_p = pad_row_ids(rows, block_samples)  # masked in-kernel via n_valid
    rownnz_b = jnp.diff(b_rpt)
    z_b, f_b = pl.pallas_call(
        functools.partial(_kernel, block_samples=block_samples,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                          n_valid=s),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_samples,), lambda i: (i,)),  # rows: blocked
            pl.BlockSpec(memory_space=pl.ANY),               # a_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # a_col
            pl.BlockSpec(memory_space=pl.ANY),               # b_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # b_col
            pl.BlockSpec(memory_space=pl.ANY),               # rownnz_b
        ],
        out_specs=[pl.BlockSpec((1,), lambda i: (i,)),
                   pl.BlockSpec((1,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((nblocks,), jnp.int32),
                   jax.ShapeDtypeStruct((nblocks,), jnp.int32)],
        interpret=interpret,
    )(rows_p, a_rpt, a_col, b_rpt, b_col, rownnz_b)
    return z_b.sum(), f_b.sum()
