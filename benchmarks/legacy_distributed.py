"""Distributed SpGEMM — the LEGACY global-pad shard path (BENCHMARK BASELINE).

Retired from the library (it lived at ``repro.core.distributed`` through
PR 4): one global ``row_capacity`` (sized by the worst predicted row in the
whole matrix) and one global-degree sort-merge pass per shard, with A AND B
fully replicated to every device.  It survives only here, as the baseline
``benchmarks/distributed_bench.py`` / ``benchmarks/comm_bench.py`` measure
the unified pipeline against; library code uses the planner/executor in
:mod:`repro.core.plan` (DESIGN.md §6–§8), which runs each shard through the
binned routed kernels with per-bucket-per-shard capacities — and, with
``n_panels``, column-partitions B instead of replicating it::

    plan = plan_spgemm(a, b, mesh=mesh,
                       pop_quant=True,      # pow2-quantized plan-cache keys
                       retry_safety=1.5)    # overflow re-planning loop
    out  = execute(plan, a, b)        # DistSpgemmOut, per-shard overflow
    c    = reassemble(plan, out)

(This legacy path only *surfaces* overflow through ``reassemble``; the
unified pipeline's armed retry loop re-executes the overflowing buckets
instead — DESIGN.md §7.)

The original paper pipeline at pod scale (DESIGN §3/§4):

  1. predict the output structure (sampled CR, eq. 4) on host,
  2. partition output rows into `data`-axis shards with ~equal PREDICTED
     output nnz (not FLOP — FLOP-balancing mis-sizes shards by exactly the
     compression ratio the paper predicts),
  3. size the per-shard static output buffers from the prediction,
  4. shard_map the numeric phase: each device computes its row range with
     the sort-merge accumulator; no cross-device traffic in the numeric
     phase (A/B index arrays are broadcast once).

Returns per-shard padded CSR blocks + the partition (for reassembly).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sparse.formats import CSR
from repro.core import csr as csr_mod
from repro.core import oracle
from repro.core import partition as part_mod
from repro.core.spgemm import gather_products, _accumulate_block


@dataclasses.dataclass
class DistSpGEMMPlan:
    row_table: np.ndarray      # (shards, rows_per_shard) int32
    row_valid: np.ndarray      # (shards, rows_per_shard) bool
    row_capacity: int
    partition: part_mod.Partition
    predicted_nnz: float


def plan_distributed(a: CSR, b: CSR, num_shards: int, *, seed: int = 0,
                     safety: float = 1.3) -> DistSpGEMMPlan:
    flopr, _ = oracle.flop_per_row(a, b)
    pred = oracle.proposed_predict(a, b, seed=seed)
    part = part_mod.balanced_contiguous(pred.structure, num_shards)
    rows_per_shard = int(max(np.diff(part.bounds).max(), 1))
    table = part_mod.static_row_assignment(part, rows_per_shard)
    valid = np.zeros_like(table, dtype=bool)
    for i in range(num_shards):
        n = int(part.bounds[i + 1] - part.bounds[i])
        valid[i, :min(n, rows_per_shard)] = True
    plan_cap = int(min(np.ceil(pred.structure.max() * safety),
                       flopr.max()))
    plan_cap = max(8, -(-plan_cap // 8) * 8)
    return DistSpGEMMPlan(table, valid, plan_cap, part, float(pred.nnz_total))


def distributed_spgemm(a: CSR, b: CSR, mesh, plan: DistSpGEMMPlan, *,
                       axis: str = "data", max_deg_a: int | None = None,
                       max_deg_b: int | None = None):
    """Run the numeric phase across ``mesh[axis]`` shards.

    Returns (col (S, R, cap), val (S, R, cap), row_nnz (S, R), overflow (S,)).
    """
    mda = max_deg_a or max(1, int(a.row_nnz.max(initial=0)))
    mdb = max_deg_b or max(1, int(b.row_nnz.max(initial=0)))
    ad = csr_mod.to_device(a)
    bd = csr_mod.to_device(b)
    rows = jnp.asarray(plan.row_table)
    cap = plan.row_capacity

    def shard_fn(rows_blk):
        # rows_blk: (1, rows_per_shard) — this shard's rows
        cols, vals, _ = gather_products(ad, bd, rows_blk[0], mda, mdb)
        oc, ov, nnz, ofl = _accumulate_block(cols, vals, cap)
        return (oc[None], ov[None], nnz[None], ofl[None])

    spec_in = P(axis, None)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec_in,),
                       out_specs=(P(axis, None, None), P(axis, None, None),
                                  P(axis, None), P(axis)),
                       check_vma=False)
    oc, ov, nnz, ofl = jax.jit(fn)(rows)
    return oc, ov, nnz, ofl


def reassemble(plan: DistSpGEMMPlan, col, val, row_nnz, ncols: int, *,
               overflow=None, on_overflow: str = "raise") -> CSR:
    """Host-side: stitch shard outputs back into one CSR (tests/examples).

    Pass the per-shard ``overflow`` array from :func:`distributed_spgemm`
    to surface dropped entries: nonzero overflow RAISES by default instead
    of silently returning a truncated matrix (``on_overflow="ignore"``
    opts back into truncation).  Omitting ``overflow`` keeps the legacy
    no-check behavior.
    """
    if overflow is not None:
        from repro.core.plan import _check_overflow
        _check_overflow(int(np.asarray(overflow).sum()), overflow,
                        on_overflow)
    # seed with typed empties: all-empty shard outputs (every row zero nnz,
    # or no valid rows at all) must reassemble to an empty CSR, not crash
    # np.concatenate on an empty list
    rows_out = [np.zeros(0, np.int64)]
    cols_out = [np.zeros(0, np.int64)]
    vals_out = [np.zeros(0, np.float32)]
    col = np.asarray(col)
    val = np.asarray(val)
    for s in range(plan.row_table.shape[0]):
        for r in range(plan.row_table.shape[1]):
            if not plan.row_valid[s, r]:
                continue
            rid = int(plan.row_table[s, r])
            c = col[s, r]
            m = c != csr_mod.COL_SENTINEL
            rows_out.append(np.full(int(m.sum()), rid, dtype=np.int64))
            cols_out.append(c[m].astype(np.int64))
            vals_out.append(val[s, r][m])
    nrows = int(plan.partition.bounds[-1])
    return CSR.from_coo(np.concatenate(rows_out), np.concatenate(cols_out),
                        np.concatenate(vals_out).astype(np.float32),
                        (nrows, ncols), dedup=False)
