"""Pallas kernels: the accumulator (SPA) route of the hybrid backend.

DESIGN.md §5: per-bucket routing replaces sort-everything.  The ESC kernels
(``spgemm_symbolic`` / ``spgemm_numeric``) pay O(w·log²w) bitonic stages per
expanded ``(rows, w)`` buffer; when B's column space is compact a dense
accumulator does the same work in O(w + N) lane-ops with no sort:

  * symbolic — **bitmask popcount**: pack B's column space into
    ``ceil(N/32)`` uint32 word lanes per row, OR each gathered product
    column's bit in (broadcast-compare + log-tree OR: static shapes, no
    scatter, VPU-only), then popcount → exact distinct count ``z*``;
  * numeric — **dense SPA**: one-hot-accumulate value products into a
    ``(block_rows, tile_n)`` dense accumulator (column-tiled over a second
    grid axis when ``next_pow2(ncols_b)`` exceeds the VMEM lane budget),
    track structural presence separately, and let the caller compact into
    the predicted ``row_capacity`` slots (``core.spgemm.compact_dense``).

Both kernels share the product gather of the ESC kernels, so z*/f* equal the
sort path bit for bit (distinct counts are order-invariant) and the numeric
outputs match to float tolerance with identical ``row_nnz``/overflow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.csr import COL_SENTINEL, pad_row_ids
from .sortnet import next_pow2, pad_to_pow2

# Cap on the broadcast-compare intermediate (rows·chunk·lanes elements) —
# keeps the 3D one-hot tensors a few MB of VMEM; wider buffers fall back to
# chunked accumulation over the product axis.
_CHUNK_ELEMS = 1 << 21


def _popcount32(v: jax.Array) -> jax.Array:
    """Per-lane population count of a uint32 array (SWAR bit-twiddle —
    static shifts/masks only, Pallas-safe on backends without a native op)."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> jnp.uint32(24)).astype(jnp.int32)


def _or_fold(x: jax.Array) -> jax.Array:
    """Bitwise-OR reduction over a pow2-sized axis 1 via log-step halving
    (static reshapes, no data-dependent control flow)."""
    while x.shape[1] > 1:
        h = x.shape[1] // 2
        x = x.reshape(x.shape[0], h, 2, *x.shape[2:])
        x = x[:, :, 0] | x[:, :, 1]
    return x[:, 0]


def _chunk_of(rows: int, lanes: int, width: int) -> int:
    """Largest pow2 chunk of the product axis keeping rows·chunk·lanes small."""
    chunk = width
    while rows * chunk * lanes > _CHUNK_ELEMS and chunk > 1:
        chunk //= 2
    return chunk


def _gather_block(rows, row_ok, a_rpt_ref, a_col_ref, b_rpt_ref, b_col_ref,
                  rownnz_b_ref, max_deg_a: int, max_deg_b: int,
                  a_val_ref=None, b_val_ref=None):
    """The shared in-kernel product gather (mirrors the ESC kernels).

    Returns ``(cols (BS, DA·DB), vals|None, deg_b (BS, DA))`` — rows with
    ``row_ok`` False (block padding) gather nothing.
    """
    bs = rows.shape[0]
    deg_a = a_rpt_ref[rows + 1] - a_rpt_ref[rows]
    ia = jax.lax.broadcasted_iota(jnp.int32, (bs, max_deg_a), 1)
    idx_a = jnp.clip(a_rpt_ref[rows][:, None] + ia, 0, a_col_ref.shape[0] - 1)
    valid_a = row_ok[:, None] & (ia < deg_a[:, None])
    ks = jnp.where(valid_a, a_col_ref[idx_a], 0)

    deg_b = jnp.where(valid_a, rownnz_b_ref[ks], 0)
    ib = jax.lax.broadcasted_iota(jnp.int32, (bs, max_deg_a, max_deg_b), 2)
    idx_b = jnp.clip(b_rpt_ref[ks][:, :, None] + ib, 0, b_col_ref.shape[0] - 1)
    valid = valid_a[:, :, None] & (ib < deg_b[:, :, None])
    cols = jnp.where(valid, b_col_ref[idx_b], COL_SENTINEL)
    vals = None
    if a_val_ref is not None:
        av = jnp.where(valid_a, a_val_ref[idx_a], 0.0)
        vals = jnp.where(valid, av[:, :, None] * b_val_ref[idx_b], 0.0)
    f = max_deg_a * max_deg_b
    cols = cols.reshape(bs, f)
    if vals is not None:
        vals = vals.reshape(bs, f)
    return cols, vals, deg_b


def extent_relative(cols: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Shift each row's columns to its own minimum: ``(rel_cols, lo)``.

    The planner bounds every bucket row's product-column *extent*
    (``RowBucket.span``), so the bitmask words / dense tile only need to
    cover that extent, not B's full column space — the lever that makes the
    SPA route O(w + extent) instead of O(w + N) on banded/FEM structure.
    Sentinel padding stays sentinel (never lands in any window; rows with no
    products keep an all-sentinel buffer and get offset 0).  THE definition
    of the relative-addressing contract — shared by the Pallas kernels and
    the jnp SPA paths in ``core.spgemm`` so they cannot diverge."""
    lo = jnp.min(cols, axis=-1)                           # sentinel if empty
    rel = jnp.where(cols == COL_SENTINEL, COL_SENTINEL, cols - lo[:, None])
    return rel, jnp.where(lo == COL_SENTINEL, 0, lo)


def _rel_cols(cols: jax.Array) -> jax.Array:
    return extent_relative(cols)[0]


def bitmask_distinct(cols: jax.Array, n_words: int) -> jax.Array:
    """Distinct count per row of a sentinel-padded column buffer.

    Broadcast-compare each product column's bit into its extent-relative
    word lane, log-tree OR over the product axis, popcount the packed
    bitmask.  O(w·span/32) lane cost with no sort — the replacement for
    bitonic + adjacent-unique wherever the extent is narrow.  Sentinel slots
    target word ``2^26``-ish and never match.  Pure jnp (static shapes, no
    scatter): runs inside Pallas kernel bodies AND as the SPA route's jnp
    path (``core.predictor.count_distinct_dense``).
    """
    bs = cols.shape[0]
    colsp, _ = pad_to_pow2(cols, None, COL_SENTINEL)
    rel = _rel_cols(colsp)
    w2 = colsp.shape[1]
    word = rel >> 5                                       # (BS, W2)
    bitval = jnp.uint32(1) << (rel & 31).astype(jnp.uint32)
    chunk = _chunk_of(bs, n_words, w2)
    mask = jnp.zeros((bs, n_words), jnp.uint32)
    for c0 in range(0, w2, chunk):
        wd = word[:, c0:c0 + chunk]
        bv = bitval[:, c0:c0 + chunk]
        iota_w = jax.lax.broadcasted_iota(jnp.int32,
                                          (bs, wd.shape[1], n_words), 2)
        contrib = jnp.where(wd[:, :, None] == iota_w, bv[:, :, None],
                            jnp.uint32(0))
        mask = mask | _or_fold(contrib)
    return _popcount32(mask).sum(axis=-1)


def _bitmask_kernel(rows_ref, a_rpt_ref, a_col_ref, b_rpt_ref, b_col_ref,
                    rownnz_b_ref, z_ref, f_ref, *, block_samples: int,
                    max_deg_a: int, max_deg_b: int, n_words: int,
                    n_valid: int):
    i = pl.program_id(0)
    pos = i * block_samples + jax.lax.broadcasted_iota(
        jnp.int32, (block_samples,), 0)
    row_ok = pos < n_valid            # block-padding rows contribute nothing
    rows = rows_ref[...]
    cols, _, deg_b = _gather_block(rows, row_ok, a_rpt_ref, a_col_ref,
                                   b_rpt_ref, b_col_ref, rownnz_b_ref,
                                   max_deg_a, max_deg_b)
    z_ref[...] = bitmask_distinct(cols, n_words).sum(keepdims=True)
    f_ref[...] = deg_b.astype(jnp.int32).sum(axis=-1).sum(keepdims=True)


def _fused_bitmask_kernel(rows_ref, a_rpt_ref, a_col_ref, b_rpt_ref,
                          b_col_ref, rownnz_b_ref, z_ref, f_ref, flop_ref, *,
                          block_samples: int, max_deg_a: int, max_deg_b: int,
                          n_words: int, n_valid: int):
    """Fused Algorithm 1 + bitmask Algorithm 2 — the SPA twin of
    ``spgemm_symbolic._fused_kernel`` (same outputs, no sort)."""
    i = pl.program_id(0)
    pos = i * block_samples + jax.lax.broadcasted_iota(
        jnp.int32, (block_samples,), 0)
    row_ok = pos < n_valid
    rows = rows_ref[...]
    cols, _, deg_b = _gather_block(rows, row_ok, a_rpt_ref, a_col_ref,
                                   b_rpt_ref, b_col_ref, rownnz_b_ref,
                                   max_deg_a, max_deg_b)
    flop = deg_b.sum(axis=-1).astype(jnp.int32)           # (BS,)
    z_ref[...] = bitmask_distinct(cols, n_words).sum(keepdims=True)
    f_ref[...] = flop.sum(keepdims=True)
    flop_ref[...] = flop


def _symbolic_call(kernel, outs, a_rpt, a_col, b_rpt, b_col, rows, *,
                   max_deg_a, max_deg_b, ncols_b, span, block_samples,
                   interpret, rownnz_b):
    s = rows.shape[0]
    nblocks = -(-s // block_samples)
    rows_p = pad_row_ids(rows, block_samples)
    if rownnz_b is None:
        rownnz_b = jnp.diff(b_rpt)
    span = int(min(span, ncols_b) if span else ncols_b)
    n_words = -(-span // 32)
    out_specs = [pl.BlockSpec((1,), lambda i: (i,)),
                 pl.BlockSpec((1,), lambda i: (i,))]
    out_shape = [jax.ShapeDtypeStruct((nblocks,), jnp.int32),
                 jax.ShapeDtypeStruct((nblocks,), jnp.int32)]
    if outs == 3:
        out_specs.append(pl.BlockSpec((block_samples,), lambda i: (i,)))
        out_shape.append(jax.ShapeDtypeStruct((nblocks * block_samples,),
                                              jnp.int32))
    return pl.pallas_call(
        functools.partial(kernel, block_samples=block_samples,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                          n_words=n_words, n_valid=s),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_samples,), lambda i: (i,)),  # rows: blocked
            pl.BlockSpec(memory_space=pl.ANY),               # a_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # a_col
            pl.BlockSpec(memory_space=pl.ANY),               # b_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # b_col
            pl.BlockSpec(memory_space=pl.ANY),               # rownnz_b
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(rows_p, a_rpt, a_col, b_rpt, b_col, rownnz_b)


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "ncols_b", "span", "block_samples",
    "interpret"))
def bitmask_symbolic_pallas(a_rpt, a_col, b_rpt, b_col, rows, *,
                            max_deg_a: int, max_deg_b: int, ncols_b: int,
                            span: int = 0, block_samples: int = 8,
                            interpret: bool, rownnz_b=None):
    """(z*, f*) via bitmask popcount — bit-equal to the sort kernel.

    ``span`` is the planner's bound on per-row product-column extent
    (``RowBucket.span``); 0 falls back to the full column space."""
    z_b, f_b = _symbolic_call(_bitmask_kernel, 2, a_rpt, a_col, b_rpt, b_col,
                              rows, max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                              ncols_b=ncols_b, span=span,
                              block_samples=block_samples,
                              interpret=interpret, rownnz_b=rownnz_b)
    return z_b.sum(), f_b.sum()


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "ncols_b", "span", "block_samples",
    "interpret"))
def fused_flop_symbolic_bitmask_pallas(a_rpt, a_col, b_rpt, b_col, rows, *,
                                       max_deg_a: int, max_deg_b: int,
                                       ncols_b: int, span: int = 0,
                                       block_samples: int = 8,
                                       interpret: bool, rownnz_b=None):
    """One pallas_call → (z*, f*, flop-per-sampled-row) — the SPA route of
    the binned predictor's fused per-bucket invocation."""
    s = rows.shape[0]
    z_b, f_b, flop = _symbolic_call(
        _fused_bitmask_kernel, 3, a_rpt, a_col, b_rpt, b_col, rows,
        max_deg_a=max_deg_a, max_deg_b=max_deg_b, ncols_b=ncols_b, span=span,
        block_samples=block_samples, interpret=interpret, rownnz_b=rownnz_b)
    return z_b.sum(), f_b.sum(), flop[:s]


def _accumulate_tile(rel, valsp, col0: int, tile_n: int, block_rows: int):
    """One-hot-accumulate a padded product buffer into the dense column tile
    ``[col0, col0+tile_n)`` of the extent-relative window: ``(acc, pres)``.
    Shared by the SPA kernel (one tile per grid step) and the BIN kernel
    (all bins per grid step, one gather)."""
    w2 = rel.shape[1]
    chunk = _chunk_of(block_rows, tile_n, w2)
    acc = jnp.zeros((block_rows, tile_n), jnp.float32)
    pres = jnp.zeros((block_rows, tile_n), jnp.bool_)
    for c0 in range(0, w2, chunk):
        c = rel[:, c0:c0 + chunk]
        v = valsp[:, c0:c0 + chunk]
        iota_n = col0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, c.shape[1], tile_n), 2)
        hit = c[:, :, None] == iota_n                     # (BS, chunk, TN)
        acc = acc + jnp.where(hit, v[:, :, None], 0.0).sum(axis=1)
        pres = pres | hit.any(axis=1)
    return acc, pres


def _spa_numeric_kernel(rows_ref, a_rpt_ref, a_col_ref, a_val_ref, b_rpt_ref,
                        b_col_ref, b_val_ref, rownnz_b_ref, acc_ref, pres_ref,
                        lo_ref, *, block_rows: int, max_deg_a: int,
                        max_deg_b: int, tile_n: int):
    """Grid step (i, t): one-hot-accumulate row block ``i``'s value products
    into extent-relative dense column tile ``t`` — values and structural
    presence separately (a cancellation summing to 0.0 is still an output
    entry, as on ESC).  Per-row column offsets come out in ``lo`` so the
    caller's compaction can restore absolute column ids."""
    rows = rows_ref[...]
    row_ok = jnp.ones((block_rows,), jnp.bool_)   # pads handled by the caller
    cols, vals, _ = _gather_block(rows, row_ok, a_rpt_ref, a_col_ref,
                                  b_rpt_ref, b_col_ref, rownnz_b_ref,
                                  max_deg_a, max_deg_b,
                                  a_val_ref=a_val_ref, b_val_ref=b_val_ref)
    colsp, valsp = pad_to_pow2(cols, vals, COL_SENTINEL)
    rel, lo = extent_relative(colsp)
    acc, pres = _accumulate_tile(rel, valsp, pl.program_id(1) * tile_n,
                                 tile_n, block_rows)
    acc_ref[...] = acc
    pres_ref[...] = pres.astype(jnp.int32)
    lo_ref[...] = lo


def _bin_numeric_kernel(rows_ref, a_rpt_ref, a_col_ref, a_val_ref, b_rpt_ref,
                        b_col_ref, b_val_ref, rownnz_b_ref, acc_ref, pres_ref,
                        lo_ref, *, block_rows: int, max_deg_a: int,
                        max_deg_b: int, tile_n: int, n_bins: int):
    """Grid step (i,): propagation-blocking accumulate of row block ``i``.

    The SPA kernel's (i, t) grid re-runs the product gather for EVERY column
    tile; on wide extents that re-gather dominates.  Here the grid is row
    blocks only: gather ONCE, then stream the padded product buffer into
    ``n_bins`` fixed-width column bins with sequential slice writes into the
    block's output row — the propagation-blocking layout of arXiv:2002.11302
    (bin first, merge each bin with a dense pass).  The per-bin merge reuses
    :func:`_accumulate_tile`, so outputs are bit-identical in structure to
    the SPA path and compact through the same ``compact_dense``."""
    rows = rows_ref[...]
    row_ok = jnp.ones((block_rows,), jnp.bool_)   # pads handled by the caller
    cols, vals, _ = _gather_block(rows, row_ok, a_rpt_ref, a_col_ref,
                                  b_rpt_ref, b_col_ref, rownnz_b_ref,
                                  max_deg_a, max_deg_b,
                                  a_val_ref=a_val_ref, b_val_ref=b_val_ref)
    colsp, valsp = pad_to_pow2(cols, vals, COL_SENTINEL)
    rel, lo = extent_relative(colsp)
    for t in range(n_bins):                       # static unroll over bins
        acc, pres = _accumulate_tile(rel, valsp, t * tile_n,
                                     tile_n, block_rows)
        acc_ref[:, t * tile_n:(t + 1) * tile_n] = acc
        pres_ref[:, t * tile_n:(t + 1) * tile_n] = pres.astype(jnp.int32)
    lo_ref[...] = lo


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "ncols_b", "tile_n", "n_tiles", "block_rows",
    "interpret"))
def spa_numeric_pallas(a_rpt, a_col, a_val, b_rpt, b_col, b_val, rows, *,
                       max_deg_a: int, max_deg_b: int, ncols_b: int,
                       tile_n: int, n_tiles: int = 0, block_rows: int = 8,
                       interpret: bool, rownnz_b=None):
    """Dense accumulator + presence + per-row column offsets for ``rows``:
    ``(acc, present, lo)`` with ``acc``/``present`` of shape
    ``(R, n_tiles·tile_n)`` covering each row's product-column extent
    relative to its own minimum column ``lo``; compaction into the predicted
    capacities is the cheap XLA pass ``core.spgemm.compact_dense`` (the same
    kernel/XLA split as the ESC numeric path).

    ``n_tiles·tile_n`` must bound every row's column extent — the planner
    guarantees that for bucket calls (``RowBucket.span``); the default
    ``n_tiles`` covers the full column space, which is always safe."""
    r = rows.shape[0]
    nblocks = -(-r // block_rows)
    pad_r = nblocks * block_rows
    rows_p = pad_row_ids(rows, block_rows)
    if rownnz_b is None:
        rownnz_b = jnp.diff(b_rpt)
    if n_tiles <= 0:
        n_tiles = -(-int(ncols_b) // tile_n)
    acc, pres, lo = pl.pallas_call(
        functools.partial(_spa_numeric_kernel, block_rows=block_rows,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                          tile_n=tile_n),
        grid=(nblocks, n_tiles),
        in_specs=[
            pl.BlockSpec((block_rows,), lambda i, t: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),               # a_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # a_col
            pl.BlockSpec(memory_space=pl.ANY),               # a_val
            pl.BlockSpec(memory_space=pl.ANY),               # b_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # b_col
            pl.BlockSpec(memory_space=pl.ANY),               # b_val
            pl.BlockSpec(memory_space=pl.ANY),               # rownnz_b
        ],
        out_specs=[pl.BlockSpec((block_rows, tile_n), lambda i, t: (i, t)),
                   pl.BlockSpec((block_rows, tile_n), lambda i, t: (i, t)),
                   pl.BlockSpec((block_rows,), lambda i, t: (i,))],
        out_shape=[
            jax.ShapeDtypeStruct((pad_r, n_tiles * tile_n), jnp.float32),
            jax.ShapeDtypeStruct((pad_r, n_tiles * tile_n), jnp.int32),
            jax.ShapeDtypeStruct((pad_r,), jnp.int32),
        ],
        interpret=interpret,
    )(rows_p, a_rpt, a_col, a_val, b_rpt, b_col, b_val, rownnz_b)
    return acc[:r], pres[:r], lo[:r]


@functools.partial(jax.jit, static_argnames=(
    "max_deg_a", "max_deg_b", "ncols_b", "tile_n", "n_tiles", "block_rows",
    "interpret"))
def bin_numeric_pallas(a_rpt, a_col, a_val, b_rpt, b_col, b_val, rows, *,
                       max_deg_a: int, max_deg_b: int, ncols_b: int,
                       tile_n: int, n_tiles: int = 0, block_rows: int = 8,
                       interpret: bool, rownnz_b=None):
    """Propagation-blocking numeric accumulate: ``(acc, present, lo)`` with
    the SAME output contract as :func:`spa_numeric_pallas` — ``(R,
    n_tiles·tile_n)`` extent-relative dense window + per-row offsets — but a
    row-blocks-only grid that gathers each block's products once and writes
    all ``n_tiles`` bins sequentially (``_bin_numeric_kernel``).  The caller
    holds ``block_rows · n_tiles·tile_n`` lanes in VMEM, so the planner
    refits ``block_rows`` against the FULL bin span (``binning.build_plan``).
    """
    r = rows.shape[0]
    nblocks = -(-r // block_rows)
    pad_r = nblocks * block_rows
    rows_p = pad_row_ids(rows, block_rows)
    if rownnz_b is None:
        rownnz_b = jnp.diff(b_rpt)
    if n_tiles <= 0:
        n_tiles = -(-int(ncols_b) // tile_n)
    span_cols = n_tiles * tile_n
    acc, pres, lo = pl.pallas_call(
        functools.partial(_bin_numeric_kernel, block_rows=block_rows,
                          max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                          tile_n=tile_n, n_bins=n_tiles),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),               # a_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # a_col
            pl.BlockSpec(memory_space=pl.ANY),               # a_val
            pl.BlockSpec(memory_space=pl.ANY),               # b_rpt
            pl.BlockSpec(memory_space=pl.ANY),               # b_col
            pl.BlockSpec(memory_space=pl.ANY),               # b_val
            pl.BlockSpec(memory_space=pl.ANY),               # rownnz_b
        ],
        out_specs=[pl.BlockSpec((block_rows, span_cols), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, span_cols), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_shape=[
            jax.ShapeDtypeStruct((pad_r, span_cols), jnp.float32),
            jax.ShapeDtypeStruct((pad_r, span_cols), jnp.int32),
            jax.ShapeDtypeStruct((pad_r,), jnp.int32),
        ],
        interpret=interpret,
    )(rows_p, a_rpt, a_col, a_val, b_rpt, b_col, b_val, rownnz_b)
    return acc[:r], pres[:r], lo[:r]
