"""jit'd public wrappers for the Pallas kernels.

On CPU the kernels run with ``interpret=True``; elsewhere they lower
natively — except on TPU, whose compiler refuses them today
(:func:`require_compilable`).  Every wrapper has an identically-shaped
oracle in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.csr import CSRDevice
from repro.core.binning import ROUTE_ESC, ROUTE_SPA, ROUTE_BIN
from . import flop_per_row as _flop_k
from . import spgemm_symbolic as _sym_k
from . import spgemm_numeric as _num_k
from . import accumulator as _acc_k
from . import flash_attention as _fa_k


# Kernel entry points the TPU compiler refuses, each pinned with its refusal
# by tests/test_tpu_compile.py: in-kernel gathers index whole-array
# (``memory_space=pl.ANY``) refs with traced integers, and rank-1 row blocks
# of 8 are not a multiple of the 128-lane tiling.
TPU_REFUSED_KERNELS = (
    "flop_per_row_pallas", "flop_rows_pallas", "sampled_symbolic_pallas",
    "fused_flop_symbolic_pallas", "bitmask_symbolic_pallas",
    "fused_flop_symbolic_bitmask_pallas", "spgemm_numeric_pallas",
    "spa_numeric_pallas", "bin_numeric_pallas")


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def require_compilable() -> None:
    """Refuse ``use_kernel=True`` up front on a backend whose compiler
    rejects the kernels — a typed error before any dispatch, instead of a
    Mosaic failure inside an executor that recovery would absorb."""
    if jax.default_backend() == "tpu":
        from repro.core.errors import PlanMismatchError
        raise PlanMismatchError(
            "use_kernel=True: the TPU compiler refuses the Pallas kernels "
            f"{', '.join(TPU_REFUSED_KERNELS)}; serve with use_kernel=False "
            "(the XLA path)", kernels=list(TPU_REFUSED_KERNELS),
            backend="tpu")


def _check_route(route: str) -> None:
    # routes are static plan metadata — an unknown string would otherwise
    # silently fall through to the ESC path and mask a planner bug
    if route not in (ROUTE_ESC, ROUTE_SPA, ROUTE_BIN):
        from repro.core.errors import PlanMismatchError
        raise PlanMismatchError(f"unknown kernel route {route!r}")


def flop_per_row(a: CSRDevice, b: CSRDevice, *, block_rows: int = 256,
                 max_deg_a: int = 128) -> jax.Array:
    rownnz_b = jnp.diff(b.rpt)
    return _flop_k.flop_per_row_pallas(
        a.rpt, a.col, rownnz_b, block_rows=block_rows, max_deg_a=max_deg_a,
        interpret=_interpret())


def flop_rows(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
              max_deg_a: int, block_rows: int = 256) -> jax.Array:
    """floprC for the listed rows only (binned-pipeline flop phase)."""
    rownnz_b = jnp.diff(b.rpt)
    return _flop_k.flop_rows_pallas(
        a.rpt, a.col, rownnz_b, rows, block_rows=block_rows,
        max_deg_a=max_deg_a, interpret=_interpret())


def sampled_symbolic(a: CSRDevice, b: CSRDevice, rows: jax.Array,
                     max_deg_a: int, max_deg_b: int,
                     block_samples: int = 8) -> tuple[jax.Array, jax.Array]:
    """(z*, f*) for the proposed predictor (kernel path)."""
    return _sym_k.sampled_symbolic_pallas(
        a.rpt, a.col, b.rpt, b.col, rows, max_deg_a=max_deg_a,
        max_deg_b=max_deg_b, block_samples=block_samples,
        interpret=_interpret())


def fused_flop_symbolic(a: CSRDevice, b: CSRDevice, rows: jax.Array,
                        max_deg_a: int, max_deg_b: int,
                        block_samples: int = 8):
    """(z*, f*, flop-per-sampled-row) in ONE kernel — the binned predictor's
    per-bucket invocation (flop + symbolic share the A-row gather)."""
    return _sym_k.fused_flop_symbolic_pallas(
        a.rpt, a.col, b.rpt, b.col, rows, max_deg_a=max_deg_a,
        max_deg_b=max_deg_b, block_samples=block_samples,
        interpret=_interpret())


def bitmask_symbolic(a: CSRDevice, b: CSRDevice, rows: jax.Array,
                     max_deg_a: int, max_deg_b: int,
                     block_samples: int = 8, span: int = 0,
                     rownnz_b=None) -> tuple[jax.Array, jax.Array]:
    """(z*, f*) via the bitmask-popcount kernel (SPA symbolic route) —
    bit-equal to :func:`sampled_symbolic`.  ``span`` is the planner's bound
    on per-row product-column extent (0 → full column space)."""
    return _acc_k.bitmask_symbolic_pallas(
        a.rpt, a.col, b.rpt, b.col, rows, max_deg_a=max_deg_a,
        max_deg_b=max_deg_b, ncols_b=b.ncols, span=span,
        block_samples=block_samples, interpret=_interpret(),
        rownnz_b=rownnz_b)


def fused_flop_symbolic_routed(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                               max_deg_a: int, max_deg_b: int,
                               route: str = ROUTE_ESC, span: int = 0,
                               block_samples: int = 8, rownnz_b=None):
    """Route-dispatched fused (z*, f*, flop) — the binned predictor's single
    per-bucket Pallas invocation.  The route is static plan metadata
    (``RowBucket.route``), so dispatch costs nothing at runtime."""
    _check_route(route)
    # distinct counts are bin-invariant: the BIN route shares the SPA
    # bitmask-popcount symbolic kernel (same span-bounded word lanes)
    if route in (ROUTE_SPA, ROUTE_BIN):
        return _acc_k.fused_flop_symbolic_bitmask_pallas(
            a.rpt, a.col, b.rpt, b.col, rows, max_deg_a=max_deg_a,
            max_deg_b=max_deg_b, ncols_b=b.ncols, span=span,
            block_samples=block_samples, interpret=_interpret(),
            rownnz_b=rownnz_b)
    return _sym_k.fused_flop_symbolic_pallas(
        a.rpt, a.col, b.rpt, b.col, rows, max_deg_a=max_deg_a,
        max_deg_b=max_deg_b, block_samples=block_samples,
        interpret=_interpret())


def spgemm_numeric(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                   max_deg_a: int, max_deg_b: int, row_capacity: int,
                   block_rows: int = 8, rownnz_b=None):
    """Kernel numeric phase + XLA compaction → (col, val, row_nnz, overflow)."""
    cols, vals, first = _num_k.spgemm_numeric_pallas(
        a.rpt, a.col, a.val, b.rpt, b.col, b.val, rows,
        max_deg_a=max_deg_a, max_deg_b=max_deg_b, block_rows=block_rows,
        interpret=_interpret(), rownnz_b=rownnz_b)
    return _num_k.compact(cols, vals, first, row_capacity)


def spgemm_numeric_spa(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                       max_deg_a: int, max_deg_b: int, row_capacity: int,
                       tile_n: int, n_tiles: int = 0, block_rows: int = 8,
                       span: int = 0, rownnz_b=None):
    """Dense-SPA kernel numeric phase + XLA compaction — same output
    contract as :func:`spgemm_numeric` (col/row_nnz/overflow identical,
    values to float tolerance).  ``n_tiles·tile_n`` must bound every row's
    product-column extent; the default tiles the planner's ``span`` bound
    (the banded/FEM lever), or the full column space when no span is
    known."""
    from repro.core.spgemm import compact_dense
    if tile_n <= 0:
        from repro.core.binning import spa_tile, DEFAULT_LANE_BUDGET
        tile_n, n_tiles = spa_tile(min(span, b.ncols) if span else b.ncols,
                                   DEFAULT_LANE_BUDGET)
    acc, pres, lo = _acc_k.spa_numeric_pallas(
        a.rpt, a.col, a.val, b.rpt, b.col, b.val, rows,
        max_deg_a=max_deg_a, max_deg_b=max_deg_b, ncols_b=b.ncols,
        tile_n=tile_n, n_tiles=n_tiles, block_rows=block_rows,
        interpret=_interpret(), rownnz_b=rownnz_b)
    return compact_dense(acc, pres.astype(bool), row_capacity, col_offset=lo)


def spgemm_numeric_bin(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                       max_deg_a: int, max_deg_b: int, row_capacity: int,
                       tile_n: int, n_tiles: int = 0, block_rows: int = 8,
                       span: int = 0, rownnz_b=None):
    """Propagation-blocking kernel numeric phase + XLA compaction — same
    output contract as :func:`spgemm_numeric` (col/row_nnz/overflow
    identical, values to float tolerance).  The kernel gathers each row
    block's products ONCE and streams them into ``n_tiles`` sequential
    column bins; compaction over the full bin window is the same
    ``compact_dense`` pass as SPA (the retained set equals ESC's)."""
    from repro.core.spgemm import compact_dense
    if tile_n <= 0:
        from repro.core.binning import bin_tile, DEFAULT_LANE_BUDGET
        tile_n, n_tiles = bin_tile(min(span, b.ncols) if span else b.ncols,
                                   DEFAULT_LANE_BUDGET)
    acc, pres, lo = _acc_k.bin_numeric_pallas(
        a.rpt, a.col, a.val, b.rpt, b.col, b.val, rows,
        max_deg_a=max_deg_a, max_deg_b=max_deg_b, ncols_b=b.ncols,
        tile_n=tile_n, n_tiles=n_tiles, block_rows=block_rows,
        interpret=_interpret(), rownnz_b=rownnz_b)
    return compact_dense(acc, pres.astype(bool), row_capacity, col_offset=lo)


def spgemm_numeric_routed(a: CSRDevice, b: CSRDevice, rows: jax.Array, *,
                          max_deg_a: int, max_deg_b: int, row_capacity: int,
                          block_rows: int = 8, route: str = ROUTE_ESC,
                          tile_n: int = 0, n_tiles: int = 0, span: int = 0,
                          rownnz_b=None):
    """Route-dispatched numeric phase — ``spgemm_binned``'s per-bucket
    kernel entry point."""
    _check_route(route)
    if route == ROUTE_SPA:
        return spgemm_numeric_spa(
            a, b, rows, max_deg_a=max_deg_a, max_deg_b=max_deg_b,
            row_capacity=row_capacity, tile_n=tile_n, n_tiles=n_tiles,
            block_rows=block_rows, span=span, rownnz_b=rownnz_b)
    if route == ROUTE_BIN:
        return spgemm_numeric_bin(
            a, b, rows, max_deg_a=max_deg_a, max_deg_b=max_deg_b,
            row_capacity=row_capacity, tile_n=tile_n, n_tiles=n_tiles,
            block_rows=block_rows, span=span, rownnz_b=rownnz_b)
    return spgemm_numeric(a, b, rows, max_deg_a=max_deg_a,
                          max_deg_b=max_deg_b, row_capacity=row_capacity,
                          block_rows=block_rows, rownnz_b=rownnz_b)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    return _fa_k.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=_interpret())
