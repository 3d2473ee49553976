"""Numeric SpGEMM on device (JAX), allocated from the paper's prediction.

Flow (the paper's motivating use-case, Section I):
  1. ``flop_per_row``          — upper bound / load-balance info (Algorithm 1)
  2. ``proposed_predict``      — sampled-CR output-structure prediction (eq. 4)
  3. ``AllocationPlan``        — static output capacities from the prediction
  4. ``spgemm``  (this module) — row-wise numeric phase writing into the
                                  predicted-size buffers, overflow-reported.

The numeric accumulation mirrors the symbolic TPU adaptation: expand products
into a static (rows, DA*DB) buffer, sort by column carrying values, sum each
run with a segmented scan, and place the run sums into per-row slots with a
second keyed sort (no scatter).  Overflow (a row whose
true nnz exceeds the predicted capacity) is counted and returned so callers
can re-run with a bumped plan — the compiled-program analogue of realloc.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .csr import CSRDevice, COL_SENTINEL, expand_products, pad_row_ids
from .binning import ROUTE_SPA, ROUTE_BIN
from repro.kernels.sortnet import segmented_run_sums


class SpGEMMOut(NamedTuple):
    col: jax.Array       # (M, row_capacity) int32, COL_SENTINEL padded
    val: jax.Array       # (M, row_capacity) float32
    row_nnz: jax.Array   # (M,) int32 — true nnz per row (may exceed capacity)
    overflow: jax.Array  # scalar int32 — total entries dropped for capacity


class PanelSpgemmOut(NamedTuple):
    """Column-partitioned numeric-phase output (DESIGN.md §8).

    One compacted block per (bucket, panel): ``cols[i][p]`` is
    ``(bucket_rows, cap[i, p])`` int32 (COL_SENTINEL padded, ascending
    ABSOLUTE column ids inside panel ``p``'s range).  Panels partition the
    column space, so a row's full output is the panel blocks read in panel
    order — no cross-panel merge pass is needed; ``reassemble`` (or any
    COO sort) restores the single-matrix layout bitwise.
    """

    cols: tuple          # per bucket: tuple per panel (rows, cap_ip) int32
    vals: tuple          # per bucket: tuple per panel (rows, cap_ip) float32
    row_nnz: tuple       # per bucket: tuple per panel (rows,) int32 — true
                         # per-panel nnz (may exceed the panel capacity)
    overflow: jax.Array  # scalar int32 — entries dropped across all blocks


def gather_products(a: CSRDevice, b: CSRDevice, rows: jax.Array,
                    max_deg_a: int, max_deg_b: int,
                    rownnz_b: jax.Array | None = None):
    """Columns AND value-products of all intermediate products of ``rows``
    (value-carrying view of :func:`repro.core.csr.expand_products`)."""
    return expand_products(a, b, rows, max_deg_a, max_deg_b,
                           rownnz_b=rownnz_b, with_values=True)


def place_sorted(keys, vals, row_capacity: int):
    """Write each row's entries into ``row_capacity`` slots by one keyed sort.

    ``keys`` holds an entry's column on the slots that carry one and
    ``COL_SENTINEL`` everywhere else, where ``vals`` must be 0.  Sorting the
    (key, value) pairs along the row moves the entries to the front in
    ascending column order and the sentinels to the tail; the first
    ``row_capacity`` slots are kept, sentinel/0 padded when the row is
    narrower.  Entries carry distinct keys and the sentinels identical
    payloads, so the sort need not be stable.  This is the compaction of
    every accumulator route: a vectorised sort, where slot-indexed
    ``.at[].add/.min`` scatters would serialise on the TPU.
    """
    keys, vals = jax.lax.sort((keys, vals), dimension=keys.ndim - 1,
                              is_stable=False, num_keys=1)
    if keys.shape[-1] >= row_capacity:
        return keys[:, :row_capacity], vals[:, :row_capacity]
    return pad_to_capacity(keys, vals, row_capacity)


def _accumulate_block(cols, vals, row_capacity: int):
    """Sort-merge (ESC) accumulation for one block of rows.

    A stable keyed sort orders each row's products by column (equal columns
    keep their gather order); the log-step segmented scan of
    ``kernels.sortnet`` places each run's sum at the run's first slot, its
    addition tree fixed by the run alone, so a row's values do not depend on
    the padded lane width or ``block_rows``; ``place_sorted`` then moves the
    run heads into the row's slots.  ``row_nnz`` counts the runs (may exceed
    ``row_capacity``).
    """
    c_s, v_s = jax.lax.sort((cols, vals), dimension=cols.ndim - 1,
                            is_stable=True, num_keys=1)
    first, run_sums = segmented_run_sums(c_s, v_s, COL_SENTINEL)
    out_col, out_val = place_sorted(jnp.where(first, c_s, COL_SENTINEL),
                                    jnp.where(first, run_sums, 0.0),
                                    row_capacity)
    row_nnz = first.sum(axis=-1, dtype=jnp.int32)
    overflow = jnp.maximum(row_nnz - row_capacity, 0).sum()
    return out_col, out_val, row_nnz, overflow


def _window_accumulate_block(cols, vals, n: int, row_capacity: int,
                             relative: bool):
    """Dense-window accumulation shared by the SPA and BIN jnp paths.

    Value products scatter-add into an ``(rows, n)`` window and structural
    presence is tracked separately (a run summing to 0.0 is still an output
    entry, exactly as on the sort path); both then compact into the
    predicted ``row_capacity`` slots through :func:`compact_dense`.
    Sentinel-padded products scatter out of range and are dropped.  With
    ``relative`` the window is addressed from each row's minimum column
    (``kernels.accumulator.extent_relative``).
    """
    lo = None
    if relative:
        from repro.kernels.accumulator import extent_relative
        cols, lo = extent_relative(cols)
    bs = cols.shape[0]
    rows_ix = jnp.broadcast_to(jnp.arange(bs)[:, None], cols.shape)
    acc = jnp.zeros((bs, n), jnp.float32).at[rows_ix, cols].add(
        vals, mode="drop")
    present = jnp.zeros((bs, n), jnp.bool_).at[rows_ix, cols].set(
        True, mode="drop")
    return compact_dense(acc, present, row_capacity, col_offset=lo)


def _dense_accumulate_block(cols, vals, ncols_b: int, row_capacity: int,
                            span: int = 0):
    """Dense-SPA accumulation for one block of rows (jnp path, DESIGN §5).

    The window covers B's column space, or with ``span`` (the planner's
    per-row column-extent bound) only the pow2-padded extent, addressed
    relative to each row's minimum column — the banded/FEM lever of the SPA
    route.  The output layout is the sort path's: ascending columns.
    """
    from .binning import ceil_pow2
    n = ceil_pow2(min(int(span), ncols_b)) if span else ncols_b
    return _window_accumulate_block(cols, vals, n, row_capacity,
                                    relative=bool(span))


def _bin_accumulate_block(cols, vals, row_capacity: int, tile_n: int,
                          n_tiles: int):
    """Propagation-blocking accumulation for one block of rows (jnp path).

    The bin layout of DESIGN.md §11: value products scatter into an
    extent-relative dense window of ``n_tiles`` bins × ``tile_n`` columns,
    and one sorted pass over the whole window places its present columns
    into the predicted ``row_capacity`` slots.  Bins are ordered by column
    range, so that pass keeps the sort path's global first-``row_capacity``
    ascending set, and ``row_nnz`` is the true structural count: overflow
    accounting matches ESC exactly.
    """
    return _window_accumulate_block(cols, vals, tile_n * n_tiles,
                                    row_capacity, relative=True)


def compact_dense(acc, present, row_capacity: int, col_offset=None):
    """Dense accumulator (+ presence mask) → predicted-capacity buffers.

    Shared by the jnp SPA/BIN paths and the Pallas SPA/BIN kernel wrappers:
    each present column, with its sum, is placed by :func:`place_sorted`
    (ascending columns, overflow slots past ``row_capacity`` dropped) —
    bit-identical structure to the ESC compaction; ``row_nnz`` is the
    structural count (may exceed capacity).  ``acc`` is 0 wherever
    ``present`` is False (nothing was added there), as the placement
    requires of its sentinel slots.  ``col_offset`` (per-row int32)
    restores absolute column ids when the accumulator was addressed relative
    to each row's minimum column (the extent-relative layout of
    ``kernels.accumulator.spa_numeric_pallas``).
    """
    col_ids = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    if col_offset is not None:
        col_ids = col_ids + col_offset[:, None].astype(jnp.int32)
    out_col, out_val = place_sorted(jnp.where(present, col_ids, COL_SENTINEL),
                                    acc, row_capacity)
    row_nnz = present.sum(axis=-1, dtype=jnp.int32)
    overflow = jnp.maximum(row_nnz - row_capacity, 0).sum()
    return out_col, out_val, row_nnz, overflow


def _blocked_rows(a: CSRDevice, b: CSRDevice, rows: jax.Array, body,
                  block_rows: int, row_capacity: int) -> SpGEMMOut:
    """Shared block/pad/slice scaffolding of the jnp numeric executors.

    Overflow is derived from the REAL rows' true nnz after slicing off the
    block padding — no closed-form correction inferred from the pad fill.
    (The previous correction assumed every pad row duplicates the *last*
    listed row; that holds for today's ``pad_row_ids`` but silently
    miscounts under any other fill contract — see its regression test.)
    """
    r = rows.shape[0]
    nblocks = -(-r // block_rows)
    pad_r = nblocks * block_rows
    row_ids = pad_row_ids(rows, block_rows).reshape(nblocks, block_rows)
    out_col, out_val, row_nnz, _ = jax.lax.map(body, row_ids)
    out_col = out_col.reshape(pad_r, row_capacity)[:r]
    out_val = out_val.reshape(pad_r, row_capacity)[:r]
    row_nnz = row_nnz.reshape(pad_r)[:r]
    overflow = jnp.maximum(row_nnz - row_capacity, 0).sum().astype(jnp.int32)
    return SpGEMMOut(out_col, out_val, row_nnz, overflow)


@functools.partial(jax.jit, static_argnames=("row_capacity", "max_deg_a",
                                             "max_deg_b", "block_rows"))
def spgemm_rows(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                row_capacity: int, max_deg_a: int, max_deg_b: int,
                block_rows: int = 256) -> SpGEMMOut:
    """Numeric phase (ESC/sort route) for an explicit row-id list (one degree
    bucket, or all rows).  Output row ``i`` corresponds to ``rows[i]``."""
    rownnz_b = jnp.diff(b.rpt)

    def body(block):
        cols, vals, _ = gather_products(a, b, block, max_deg_a, max_deg_b,
                                        rownnz_b=rownnz_b)
        return _accumulate_block(cols, vals, row_capacity)

    return _blocked_rows(a, b, rows, body, block_rows, row_capacity)


@functools.partial(jax.jit, static_argnames=("row_capacity", "max_deg_a",
                                             "max_deg_b", "block_rows",
                                             "span"))
def spgemm_rows_spa(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                    row_capacity: int, max_deg_a: int, max_deg_b: int,
                    block_rows: int = 256, span: int = 0) -> SpGEMMOut:
    """Numeric phase, dense-SPA route: same contract as :func:`spgemm_rows`
    (identical ``col``/``row_nnz``/``overflow``; ``val`` to float tolerance —
    the accumulation order differs).  ``span`` is the planner's bound on the
    rows' product-column extent (0 → full column space)."""
    rownnz_b = jnp.diff(b.rpt)

    def body(block):
        cols, vals, _ = gather_products(a, b, block, max_deg_a, max_deg_b,
                                        rownnz_b=rownnz_b)
        return _dense_accumulate_block(cols, vals, b.ncols, row_capacity,
                                       span)

    return _blocked_rows(a, b, rows, body, block_rows, row_capacity)


@functools.partial(jax.jit, static_argnames=("row_capacity", "max_deg_a",
                                             "max_deg_b", "block_rows",
                                             "tile_n", "n_tiles"))
def spgemm_rows_bin(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                    row_capacity: int, max_deg_a: int, max_deg_b: int,
                    block_rows: int = 256, tile_n: int = 128,
                    n_tiles: int = 1) -> SpGEMMOut:
    """Numeric phase, propagation-blocking bin route: same contract as
    :func:`spgemm_rows` (identical ``col``/``row_nnz``/``overflow``; ``val``
    to float tolerance).  ``tile_n``/``n_tiles`` are the planner's bin
    layout (``binning.bin_tile``): ``n_tiles·tile_n`` must bound the rows'
    pow2-padded product-column extent."""
    rownnz_b = jnp.diff(b.rpt)

    def body(block):
        cols, vals, _ = gather_products(a, b, block, max_deg_a, max_deg_b,
                                        rownnz_b=rownnz_b)
        return _bin_accumulate_block(cols, vals, row_capacity, tile_n,
                                     n_tiles)

    return _blocked_rows(a, b, rows, body, block_rows, row_capacity)


def spgemm(a: CSRDevice, b: CSRDevice, *, row_capacity: int,
           max_deg_a: int, max_deg_b: int, block_rows: int = 256) -> SpGEMMOut:
    """C = A·B numeric phase with predicted-capacity output buffers."""
    rows = jnp.arange(a.nrows, dtype=jnp.int32)
    return spgemm_rows(a, b, rows, row_capacity=row_capacity,
                       max_deg_a=max_deg_a, max_deg_b=max_deg_b,
                       block_rows=block_rows)


def routed_spgemm_rows(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                       row_capacity: int, deg_a: int, deg_b: int,
                       block_rows: int, route: str = "esc", tile_n: int = 0,
                       n_tiles: int = 0, span: int = 0,
                       use_kernel: bool = False) -> SpGEMMOut:
    """One bucket's numeric phase on its planned accumulator route.

    THE per-bucket dispatch shared by :func:`spgemm_binned` and the
    plan/execute executors (``core.plan``) — single and distributed callers
    running a bucket through this one function is what makes their outputs
    interchangeable (identical ``col``/``row_nnz``/``overflow``; ``val`` to
    float tolerance across routes, see DESIGN.md §5/§6).
    """
    if use_kernel:
        from repro.kernels import ops as kops
        return SpGEMMOut(*kops.spgemm_numeric_routed(
            a, b, rows, max_deg_a=deg_a, max_deg_b=deg_b,
            row_capacity=row_capacity, block_rows=block_rows,
            route=route, tile_n=tile_n, n_tiles=n_tiles, span=span))
    if route == ROUTE_SPA:
        return spgemm_rows_spa(a, b, rows, row_capacity=row_capacity,
                               max_deg_a=deg_a, max_deg_b=deg_b,
                               block_rows=block_rows, span=span)
    if route == ROUTE_BIN and tile_n:
        return spgemm_rows_bin(a, b, rows, row_capacity=row_capacity,
                               max_deg_a=deg_a, max_deg_b=deg_b,
                               block_rows=block_rows, tile_n=tile_n,
                               n_tiles=max(1, n_tiles))
    return spgemm_rows(a, b, rows, row_capacity=row_capacity,
                       max_deg_a=deg_a, max_deg_b=deg_b,
                       block_rows=block_rows)


def pad_to_capacity(c: jax.Array, v: jax.Array,
                    cap_out: int) -> tuple[jax.Array, jax.Array]:
    """Widen a bucket's ``(rows, cap)`` col/val blocks to ``cap_out`` slots
    (sentinel/zero fill) — the shared output-assembly contract of
    :func:`spgemm_binned` and the ``core.plan`` executors."""
    cap = c.shape[1]
    if cap >= cap_out:
        return c, v
    c = jnp.concatenate(
        [c, jnp.full((c.shape[0], cap_out - cap), COL_SENTINEL, jnp.int32)],
        axis=1)
    v = jnp.concatenate(
        [v, jnp.zeros((v.shape[0], cap_out - cap), jnp.float32)], axis=1)
    return c, v


def spgemm_binned(a: CSRDevice, b: CSRDevice, plan, *,
                  alloc, use_kernel: bool = False) -> SpGEMMOut:
    """C = A·B numeric phase, bucket-iterated (DESIGN.md §4).

    ``plan`` is a ``core.binning.BinningPlan``; ``alloc`` is either an int
    (uniform row capacity — output bitwise-equal to :func:`spgemm` wherever
    every bucket runs the ESC route) or a ``predictor.BinnedAllocationPlan``
    (per-bucket capacities — smaller buffers, same values wherever neither
    path overflows).  Each bucket runs its planned accumulator route — ESC
    (sort) or dense-SPA — with identical ``col``/``row_nnz``/``overflow``
    and ``val`` to float tolerance (DESIGN.md §5).  With ``use_kernel`` the
    per-bucket pass is the routed Pallas dispatch in ``kernels.ops``.
    """
    if isinstance(alloc, (int, np.integer)):
        caps = [int(alloc)] * len(plan.buckets)
        cap_out = int(alloc)        # parity with spgemm even for empty plans
    else:
        caps = list(alloc.bucket_capacities)
        cap_out = max(caps) if caps else alloc.row_capacity
    if not plan.buckets:   # empty matrix: parity with the global path
        return SpGEMMOut(jnp.full((0, cap_out), COL_SENTINEL, jnp.int32),
                         jnp.zeros((0, cap_out), jnp.float32),
                         jnp.zeros((0,), jnp.int32), jnp.int32(0))
    parts_c, parts_v, parts_n = [], [], []
    overflow = jnp.int32(0)
    for bucket, cap in zip(plan.buckets, caps):
        if bucket.n_rows == 0:
            continue
        rows_d = jnp.asarray(bucket.rows)
        c, v, n, of = routed_spgemm_rows(
            a, b, rows_d, row_capacity=cap, deg_a=bucket.deg_a,
            deg_b=bucket.deg_b, block_rows=bucket.block_rows,
            route=bucket.route, tile_n=bucket.tile_n, n_tiles=bucket.n_tiles,
            span=bucket.span, use_kernel=use_kernel)
        c, v = pad_to_capacity(c, v, cap_out)
        parts_c.append(c)
        parts_v.append(v)
        parts_n.append(n.astype(jnp.int32))
        overflow = overflow + of.astype(jnp.int32)
    # buckets partition the rows: one concat + inverse permutation assembles
    # the output (no per-bucket full-array scatter copies)
    perm = plan.inverse_perm()
    return SpGEMMOut(jnp.concatenate(parts_c, axis=0)[perm],
                     jnp.concatenate(parts_v, axis=0)[perm],
                     jnp.concatenate(parts_n, axis=0)[perm],
                     overflow)


def dense_of(out: SpGEMMOut, ncols: int) -> jax.Array:
    """Densify (tests only)."""
    m, cap = out.col.shape
    valid = out.col != COL_SENTINEL
    safe = jnp.where(valid, out.col, 0)
    rows = jnp.broadcast_to(jnp.arange(m)[:, None], (m, cap))
    return jnp.zeros((m, ncols), jnp.float32).at[rows, safe].add(
        jnp.where(valid, out.val, 0.0))
