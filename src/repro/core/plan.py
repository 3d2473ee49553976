"""Unified SpGEMM planner/executor with a signature-keyed plan cache.

This module subsumes the previously scattered plan state (``BinningPlan`` +
``AllocationPlan`` / ``BinnedAllocationPlan`` + ``DistSpGEMMPlan``) into ONE
pipeline (DESIGN.md §6) that runs the paper's whole point end to end:

  1. **sample → predict**: the binned, routed sampled-CR predictor
     (``predictor.proposed_predict_binned``, eq. 4) — not the global-pad one;
  2. **partition on predicted nnz**: output rows split into ``num_shards``
     contiguous ranges with ~equal *predicted* output nnz
     (``partition.balanced_contiguous`` — the paper's load-balance claim);
  3. **capacities per bucket per shard**: each degree bucket's output buffer
     is sized from the prediction restricted to the rows that bucket owns
     inside each shard (``predictor.shard_bucket_capacities``) — a hub row
     inflates only its own (tiny) bucket, never another shard's buffers;
  4. **execute through the binned routed kernels**: both the single-device
     and the shard_map executor run every bucket through
     ``spgemm.routed_spgemm_rows`` (ESC sort / dense-SPA dispatch, optional
     Pallas kernels via ``kernels.ops``) — the PR 1/2 wins reach pod scale.

**Plan cache.** Executors are built once per *plan key* — the static half of
the compile contract: matrix shapes, device-CSR capacities (pow2-padded so
same-family matrices share them), the ordered per-bucket
``(signature, population, capacity)`` tuples (``RowBucket.signature`` is the
``BinningPlan.signatures()`` contract from DESIGN.md §4), and the mesh
fingerprint.  Repeated SpGEMMs over same-shaped bucket sets — the serving
scenario — look up the same jitted executable and run with ZERO retraces
(``PlanCache.stats()["traces"]`` is pinned by ``tests/test_plan.py`` /
``tests/test_distributed.py``).

**Population quantization** (``plan_spgemm(pop_quant=True)``, DESIGN.md §7).
The exact-population key above limits guaranteed reuse to structure-identical
pairs.  The quantization knob pow2-pads every varying shape in the key —
bucket populations (local row tables ride with a validity mask; distributed
``rows_pb`` pads its shard tables), degree bounds
(``binning.POW2_DEG_ALIGN``) and predicted capacities — so *same-family,
different-seed* matrices share executables at ≤2× row padding (hit rates
measured in ``benchmarks/plan_cache_bench.py`` → ``BENCH_plan_cache.json``).
:class:`PlanTemplate` goes further: it freezes one quantized plan's bucket
ladder as the family-level compile contract and grows it monotonically
(pow2, in place), so EVERY member planned after the last growth shares one
executor — 100% steady-state reuse on all suite families (bench-gated).

**Overflow re-planning** (``plan_spgemm(retry_safety=...)``, DESIGN.md §7).
The numeric kernels report each row's TRUE nnz even when its bucket's
capacity truncates the output, so after the numeric phase :func:`execute`
detects per-bucket (and per-shard) overflow host-side, bumps ONLY the
overflowing buckets' capacities (``×retry_safety^n``, pow2-rounded, floored
at the observed need) and re-executes just those buckets through cached
per-bucket executors, splicing the results back — the compiled-program
analogue of realloc, closing the paper's predict→allocate loop end to end.
Retry counts and final capacities are surfaced on the plan
(``plan.retries`` / ``plan.retry_events`` / ``plan.stats()``); the
no-overflow fast path costs one host readback of ``row_nnz`` and ZERO
retraces.

Public API::

    plan = plan_spgemm(a, b)                    # single device
    out  = execute(plan, a, b)                  # SpGEMMOut
    plan = plan_spgemm(a, b, mesh=mesh)         # distributed
    out  = execute(plan, a, b)                  # DistSpgemmOut
    c    = reassemble(plan, out, ncols=b.ncols) # host CSR
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sparse.formats import CSR
from . import binning as binning_mod
from . import csr as csr_mod
from . import faults as faults_mod
from . import oracle
from . import partition as part_mod
from . import predictor as predictor_mod
from . import profiles as profiles_mod
from . import validate as validate_mod
from .csr import COL_SENTINEL, CSRDevice
from .errors import (CapacityExhaustedError, OperandValidationError,
                     PlanMismatchError, ShardFailureError, SpgemmError,
                     StragglerError)
from .spgemm import (SpGEMMOut, PanelSpgemmOut, pad_to_capacity,
                     routed_spgemm_rows)


# --------------------------------------------------------------------------- #
# Plan cache — session-level executor registry keyed on plan signatures.
# --------------------------------------------------------------------------- #
class PlanCache:
    """Maps plan keys to compiled (jitted) executors.

    ``hits``/``misses`` count executor lookups; ``traces`` counts actual
    executor retraces (the executor bodies bump it while being traced), so a
    cache-served SpGEMM over a same-shaped bucket set shows ``traces``
    unchanged — the zero-retrace serving contract.
    """

    def __init__(self) -> None:
        self._executors: dict = {}
        self.hits = 0
        self.misses = 0
        self.traces = 0

    def executor(self, key, build):
        """Get-or-build the executor for ``key`` (hashable plan key)."""
        if key in self._executors:
            self.hits += 1
        else:
            self.misses += 1
            self._executors[key] = build()
        return self._executors[key]

    def _note_trace(self) -> None:
        self.traces += 1

    def stats(self) -> dict:
        return dict(size=len(self._executors), hits=self.hits,
                    misses=self.misses, traces=self.traces)

    def clear(self) -> None:
        self._executors.clear()
        self.hits = self.misses = self.traces = 0


_DEFAULT_CACHE = PlanCache()


def plan_cache() -> PlanCache:
    """The session-level default plan cache."""
    return _DEFAULT_CACHE


# --------------------------------------------------------------------------- #
# Retry escalation policy (DESIGN.md §9)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry escalation for the overflow re-planning loop.

    Replaces the raw ``retry_safety``/``max_retries`` pair: ``rounds``
    pow2-bump ladder rounds (``×growth^attempt``, floored at the observed
    need) with an optional per-round capacity ceiling ``max_capacity``;
    when the ladder exhausts (no budget, or every bump ceiling-clamped)
    and ``exact_fallback`` is on, the loop escalates ONCE to an exact
    symbolic count (``predictor.exact_row_counts``) for only the offending
    (bucket × panel) units — guaranteed termination in ≤ ``rounds``+1
    re-execute waves with bitwise-correct output, recorded in
    ``plan.stats()["degradations"]``.  Residual overflow after that (only
    possible with the fallback off) follows ``on_exhausted``: ``"raise"``
    surfaces a typed :class:`~repro.core.errors.CapacityExhaustedError`
    (distributed: :class:`~repro.core.errors.ShardFailureError` naming the
    shard/panel); ``"surface"`` is the legacy behavior — overflow stays on
    the result and :func:`reassemble` raises.
    """

    rounds: int = 4
    growth: float = 1.5
    max_capacity: int | None = None
    exact_fallback: bool = True
    on_exhausted: str = "raise"       # "raise" | "surface"

    def __post_init__(self):
        if self.rounds < 0:
            raise PlanMismatchError(f"RetryPolicy.rounds must be >= 0, got "
                                    f"{self.rounds}")
        if self.on_exhausted not in ("raise", "surface"):
            raise PlanMismatchError(
                f"RetryPolicy.on_exhausted must be 'raise' or 'surface', "
                f"got {self.on_exhausted!r}")

    def clamp(self, cap: int, new_cap: int) -> int:
        """Apply the per-round ceiling; never shrink below the current cap."""
        if self.max_capacity is None:
            return new_cap
        return min(new_cap, max(int(self.max_capacity), cap))


@dataclasses.dataclass(frozen=True)
class DispatchBudget:
    """Straggler watchdog for executor dispatches (DESIGN.md §12).

    A plan armed with a budget (``plan_spgemm(dispatch_budget=...)``) times
    every wave/recovery dispatch through :func:`_invoke_executor` and raises
    a typed :class:`~repro.core.errors.StragglerError` when the dispatch
    exceeds ``multiple ×`` the PRICED expected seconds — per-unit seconds
    from :func:`repro.core.profiles.unit_seconds` (a measured profile when
    one is active, the analytic roofline otherwise) — floored at ``floor_s``
    so tiny dispatches never trip on scheduler noise.  Pricing rule::

        limit = max(floor_s, multiple · Σ_units unit_seconds(route, w, span, rows))

    Two deliberate exemptions keep the watchdog deterministic: a dispatch
    that TRACED (first execution of a fresh executable) never counts its
    real wall time — compile time is not a straggler — and injected
    ``delay_executor`` seconds always count, traced or not, so chaos tests
    exercise the full straggler → recovery path without real sleeps.
    """

    multiple: float = 10.0
    floor_s: float = 0.05

    def limit(self, priced_s: float) -> float:
        return max(float(self.floor_s),
                   float(self.multiple) * max(0.0, float(priced_s)))


def _plan_key_id(plan) -> str | None:
    """Short stable fingerprint of ``plan.key`` for error context."""
    try:
        return format(hash(plan.key) & 0xFFFFFFFF, "08x")
    except Exception:
        return None


# --------------------------------------------------------------------------- #
# Plan dataclasses
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class BucketShardTable:
    """One bucket's static shard execution table (distributed plans).

    ``table[s]`` lists the bucket rows shard ``s`` computes, padded to the
    bucket's max per-shard population ``rows_pb`` by repeating the shard's
    last owned row (or any bucket row when the shard owns none — padded
    outputs are masked off by ``valid`` at reassembly/overflow time).
    """

    table: np.ndarray       # (num_shards, rows_pb) int32
    valid: np.ndarray       # (num_shards, rows_pb) bool
    capacity: int           # static per-row output slots (max per-shard need)

    @property
    def rows_pb(self) -> int:
        return int(self.table.shape[1])


@dataclasses.dataclass(eq=False)   # identity compare; plans match via .key
class SpgemmPlan:
    """The unified plan: prediction + partition + capacities + executor key."""

    binning: binning_mod.BinningPlan
    alloc: predictor_mod.BinnedAllocationPlan
    structure: np.ndarray           # predicted nnz per output row (float64)
    flopr: np.ndarray               # FLOP per output row (int64)
    predicted_nnz: float
    compression_ratio: float
    sample_rows: np.ndarray
    shape_a: tuple[int, int]
    shape_b: tuple[int, int]
    cap_a: int                      # device-CSR col/val capacity (pow2-padded)
    cap_b: int
    safety: float
    use_kernel: bool
    # plan-cache quantization + overflow re-planning (DESIGN.md §7)
    pop_quant: bool = False         # pow2-padded populations/degrees/caps
    retry_safety: float = 0.0       # 0 → replanning off; else capacity bump/round
    max_retries: int = 4
    retries: int = 0                # rounds the last execute() needed
    retry_events: list = dataclasses.field(default_factory=list)  # last execute()
    # failure containment (DESIGN.md §9)
    retry_policy: "RetryPolicy | None" = None   # None → re-planning off
    degradations: list = dataclasses.field(default_factory=list)  # last execute()
    validation: dict = dataclasses.field(
        default_factory=lambda: dict(operands_validated=0,
                                     fingerprint_checks=0))
    # degraded-mesh recovery (DESIGN.md §12)
    dispatch_budget: "DispatchBudget | None" = None   # straggler watchdog
    recoveries: list = dataclasses.field(default_factory=list)  # last execute()
    # distributed-only (num_shards == 0 → single device)
    num_shards: int = 0
    axis: str = "data"
    partition: part_mod.Partition | None = None
    shard_tables: tuple[BucketShardTable, ...] = ()
    shard_capacities: np.ndarray | None = None  # (buckets, shards) per-shard need
    mesh: object = None             # not part of the key (see _mesh_key)
    # column-partitioned B (DESIGN.md §8); n_panels == 0 → replicated-B mode
    n_panels: int = 0
    panels: part_mod.PanelPartition | None = None
    panel_deg_b: tuple = ()         # per-bucket panel deg_b bound (≤ full deg_b)
    panel_caps: np.ndarray | None = None   # (buckets, n_panels) current caps
    row_shards: int = 0             # distributed: num_shards // n_panels
    _panel_host: tuple | None = dataclasses.field(default=None, repr=False)
    _panel_caps_dev: tuple = ()     # single-device per-panel operand capacities
    _panel_gather: object = None    # PanelGather (distributed numeric operands)
    # cached structure-only device uploads: gather indices (distributed) or
    # per-panel rpt/col (single-device) — the two modes are exclusive
    _panel_dev: tuple | None = dataclasses.field(default=None, repr=False)
    _nnz_b: int = 0                 # planned B nnz (panel gather map validity)
    # (nnz, col-sum) fingerprints of the PLANNED operands: the panel gather
    # maps bake both structures in, so execute() rejects a swapped operand
    # instead of silently combining it with the wrong index maps
    _panel_a_fp: tuple | None = None
    _panel_b_fp: tuple | None = None
    _template: object = None        # PlanTemplate this plan was fit against
    _pop_override: tuple | None = dataclasses.field(default=None, repr=False)
    _device_args: tuple | None = dataclasses.field(default=None, repr=False)
    # ((host_a, host_b), (ad, bd)) from planning — execute() on the planned
    # operands reuses the prediction pass's upload instead of a second H2D
    _planned_pair: tuple | None = dataclasses.field(default=None, repr=False)

    @property
    def distributed(self) -> bool:
        return self.num_shards > 0

    def local_populations(self) -> tuple[int, ...]:
        """Per-bucket traced row counts of the local executor — the exact
        populations, their pow2 pads under ``pop_quant``, or the template's
        grown pads when planned against one."""
        if self._pop_override is not None:
            return self._pop_override
        if self.pop_quant:
            return tuple(binning_mod.ceil_pow2(bk.n_rows)
                         for bk in self.binning.buckets)
        return tuple(bk.n_rows for bk in self.binning.buckets)

    def device_args(self) -> tuple:
        """Executor row-table args (+ inverse perm for local plans; + validity
        masks under ``pop_quant``), uploaded once per plan — the cache-served
        serving path pays pure dispatch."""
        if self._device_args is None:
            if self.distributed:
                args = tuple(jnp.asarray(t.table) for t in self.shard_tables)
            elif self.pop_quant:
                # pow2-padded bucket tables (repeat-last fill) + validity
                # masks; the inverse perm indexes the PADDED concatenation so
                # assembly drops pad rows for free
                pops = self.local_populations()
                tables, masks, pos = [], [], []
                off = 0
                for bk, pop in zip(self.binning.buckets, pops):
                    ids = np.empty(pop, dtype=np.int32)
                    ids[:bk.n_rows] = bk.rows
                    ids[bk.n_rows:] = bk.rows[-1] if bk.n_rows else 0
                    tables.append(jnp.asarray(ids))
                    mask = np.zeros(pop, dtype=bool)
                    mask[:bk.n_rows] = True
                    masks.append(jnp.asarray(mask))
                    pos.append(off + np.arange(bk.n_rows, dtype=np.int64))
                    off += pop
                pos = (np.concatenate(pos) if pos
                       else np.zeros(0, dtype=np.int64))
                perm = jnp.asarray(
                    pos[self.binning.inverse_perm()].astype(np.int32))
                args = (perm,) + tuple(masks) + tuple(tables)
            else:
                perm = jnp.asarray(
                    self.binning.inverse_perm().astype(np.int32))
                args = (perm,) + tuple(jnp.asarray(bk.rows)
                                       for bk in self.binning.buckets)
            self._device_args = args
        return self._device_args

    @property
    def key(self) -> tuple:
        """The static half of the compile contract (mesh fingerprint added
        at executor-lookup time, see :func:`_executor_key`)."""
        if self.n_panels:
            # panel plans key on the panel layout (quantized edges), the
            # gathered-operand statics, and per-bucket panel degree bounds
            # and capacities — the whole numeric compile contract of §8
            if self.distributed:
                buckets = tuple(
                    (bk.signature, db, t.rows_pb, t.capacity)
                    for bk, db, t in zip(self.binning.buckets,
                                         self.panel_deg_b, self.shard_tables))
                pan = (self.panels.key, self.row_shards,
                       self._panel_gather.nref, self._panel_gather.ecap)
            else:
                buckets = tuple(
                    (bk.signature, db, pop,
                     tuple(int(c) for c in self.panel_caps[i]))
                    for i, (bk, db, pop) in enumerate(
                        zip(self.binning.buckets, self.panel_deg_b,
                            self.local_populations())))
                pan = (self.panels.key, self._panel_caps_dev)
            return ("spgemm-plan-panels", self.num_shards, self.axis,
                    self.use_kernel, self.pop_quant, self.shape_a,
                    self.shape_b, self.cap_a, buckets, pan)
        if self.distributed:
            buckets = tuple(
                (bk.signature, t.rows_pb, t.capacity)
                for bk, t in zip(self.binning.buckets, self.shard_tables))
        else:
            buckets = tuple(
                (bk.signature, pop, int(cap))
                for bk, pop, cap in zip(self.binning.buckets,
                                        self.local_populations(),
                                        self.alloc.bucket_capacities))
        return ("spgemm-plan", self.num_shards, self.axis, self.use_kernel,
                self.pop_quant, self.shape_a, self.shape_b,
                self.cap_a, self.cap_b,
                self.alloc.row_capacity, buckets)

    def shard_slots(self) -> int:
        """Output slots each shard allocates under this plan
        (Σ buckets rows_pb·capacity; SPMD — identical on every shard)."""
        if not self.distributed:
            return int(self.alloc.total_capacity)
        return int(sum(t.rows_pb * t.capacity for t in self.shard_tables))

    def to_device(self, m: CSR, which: str) -> CSRDevice:
        """Convert one operand at the plan's padded device capacity."""
        cap = self.cap_a if which == "a" else self.cap_b
        shape = self.shape_a if which == "a" else self.shape_b
        validate_mod.validate_csr(m, name=which)
        if m.shape != shape:
            raise PlanMismatchError(
                f"operand {which} shape {m.shape} != planned {shape}",
                operand=which, observed=list(m.shape), planned=list(shape),
                plan_key=_plan_key_id(self))
        if m.nnz > cap:
            raise PlanMismatchError(
                f"operand {which} nnz {m.nnz} exceeds planned device "
                f"capacity {cap}", operand=which, observed=int(m.nnz),
                planned=int(cap), plan_key=_plan_key_id(self))
        return csr_mod.to_device(m, capacity=cap)

    def stats(self) -> dict:
        out = dict(
            predicted_nnz=round(float(self.predicted_nnz), 1),
            compression_ratio=round(float(self.compression_ratio), 4),
            num_buckets=len(self.binning.buckets),
            lane_reduction=round(self.binning.lane_reduction, 3),
            route_rows=self.binning.route_rows(),
            route_profile=profiles_mod.status(),
            bucket_capacities=list(self.alloc.bucket_capacities),
            total_capacity=int(self.alloc.total_capacity),
        )
        if self.distributed:
            out.update(
                num_shards=self.num_shards,
                imbalance=round(self.partition.imbalance, 4),
                shard_slots=self.shard_slots(),
                bucket_rows_per_shard=[t.rows_pb for t in self.shard_tables],
                shard_bucket_capacities=[t.capacity for t in self.shard_tables],
            )
        if self.pop_quant:
            real = max(1, sum(bk.n_rows for bk in self.binning.buckets))
            out.update(pop_quant=True,
                       row_padding=round(sum(self.local_populations()) / real, 4))
        if self.retry_safety > 0:
            out.update(
                retry_safety=self.retry_safety,
                retries=self.retries,
                retry_events=list(self.retry_events),
                final_capacities=(
                    [[int(c) for c in row] for row in self.panel_caps]
                    if self.n_panels else
                    [t.capacity for t in self.shard_tables]
                    if self.distributed else
                    list(self.alloc.bucket_capacities)),
            )
        if self.n_panels:
            out.update(
                n_panels=self.n_panels,
                panel_edges=[int(e) for e in self.panels.edges],
                panel_nnz=[int(n) for n in self.panels.panel_nnz],
            )
            if self.distributed:
                out.update(row_shards=self.row_shards,
                           comm=self.comm_stats())
        # failure-containment counters (DESIGN.md §9) — always present so
        # observability dashboards need no schema branching; every value is
        # JSON-serializable by construction.
        out.update(
            retries=int(self.retries),
            degradations=[dict(e) for e in self.degradations],
            validation=dict(self.validation),
            recoveries=[dict(e) for e in self.recoveries],
        )
        return out

    def comm_stats(self) -> dict:
        """Per-device B footprint + gather volume of a panel-distributed plan
        vs the replicated-B executor — the §8 acceptance metric
        (``benchmarks/comm_bench.py`` → ``BENCH_comm.json``)."""
        if not (self.n_panels and self.distributed):
            raise PlanMismatchError(
                "comm_stats needs a distributed panel plan",
                plan_key=_plan_key_id(self))
        pg = self._panel_gather
        # index+value bytes per entry (int32 col + float32 val) + rpt words
        rep_bytes = self.cap_b * 8 + (self.shape_b[0] + 1) * 4
        dev_bytes = pg.ecap * 8 + (pg.nref + 1) * 4
        payload_max = int(pg.ref_nnz.max()) if pg.ref_nnz.size else 0
        return dict(
            n_panels=self.n_panels,
            devices=self.num_shards,
            row_shards=self.row_shards,
            replicated_b_bytes=int(rep_bytes),
            per_device_b_bytes=int(dev_bytes),
            footprint_reduction=round(rep_bytes / max(1, dev_bytes), 3),
            b_nnz=int(self._nnz_b),
            payload_entries_max=payload_max,
            payload_reduction=round(self._nnz_b / max(1, payload_max), 3),
            gathered_entries_total=int(pg.ref_nnz.sum()),
            gathered_bytes_total=int(pg.ref_nnz.sum()) * 8,
        )


class DistSpgemmOut(NamedTuple):
    """Distributed numeric-phase output: per-bucket stacked shard blocks."""

    cols: tuple        # per bucket: (num_shards, rows_pb, cap_b) int32
    vals: tuple        # per bucket: (num_shards, rows_pb, cap_b) float32
    row_nnz: tuple     # per bucket: (num_shards, rows_pb) int32 — true nnz
    shard_overflow: np.ndarray   # (num_shards,) int64 — valid rows only


# --------------------------------------------------------------------------- #
# Plan templates — the family-level compile contract (DESIGN.md §7).
#
# Per-component pow2 rounding cannot make two matrices share a key when the
# bucket LADDER itself differs (a width band present in one seed's histogram
# and absent in the other's, or a hub degree crossing a pow2 boundary).  A
# template freezes one quantized plan's static half — bucket signatures,
# padded populations, capacities, device-CSR caps — and other same-shape
# matrices plan AGAINST it: rows are assigned to the first template bucket
# whose degree bounds dominate them, populations/capacities grow (pow2,
# monotone, in place) only when a member exceeds the template, and every
# member planned after the last growth lands on the SAME plan key.
# --------------------------------------------------------------------------- #
class PlanTemplate:
    """Mutable static execution profile shared by a family of matrices.

    Build from a representative plan, then pass to
    ``plan_spgemm(template=...)``::

        tpl = PlanTemplate.from_plan(plan_spgemm(a0, b0, pop_quant=True))
        p1  = plan_spgemm(a1, b1, template=tpl)   # same key as a0·b0's plan
                                                  # unless a1/b1 outgrow it

    Growth events (``tpl.growths``) re-key subsequent plans once; members
    planned after the last growth all share executables.
    """

    def __init__(self, shape_a, shape_b, cap_a, cap_b, use_kernel, safety,
                 sigs, pops, caps):
        self.shape_a = tuple(shape_a)
        self.shape_b = tuple(shape_b)
        self.cap_a = int(cap_a)
        self.cap_b = int(cap_b)
        self.use_kernel = bool(use_kernel)
        self.safety = float(safety)
        self.sigs = list(sigs)      # per-bucket RowBucket.signature tuples
        self.pops = list(pops)      # pow2 padded populations
        self.caps = list(caps)      # pow2 row capacities
        self.growths = 0

    @staticmethod
    def from_plan(plan: "SpgemmPlan") -> "PlanTemplate":
        if not plan.pop_quant:
            raise PlanMismatchError("templates require a pop_quant=True plan",
                                    plan_key=_plan_key_id(plan))
        if plan.distributed:
            raise PlanMismatchError(
                "build templates from a single-device plan; "
                "pass mesh to plan_spgemm(template=...) instead",
                plan_key=_plan_key_id(plan))
        return PlanTemplate(
            plan.shape_a, plan.shape_b, plan.cap_a, plan.cap_b,
            plan.use_kernel, plan.safety,
            sigs=[bk.signature for bk in plan.binning.buckets],
            pops=list(plan.local_populations()),
            caps=list(plan.alloc.bucket_capacities))

    def _grow_sig(self, i: int, da: int, db: int, span: int,
                  lane_budget: int = binning_mod.DEFAULT_LANE_BUDGET) -> None:
        """Raise bucket ``i``'s static bounds to dominate (da, db, span)."""
        da0, db0, _, route, _, span0 = self.sigs[i]
        da = max(da0, binning_mod.ceil_pow2(da))
        db = max(db0, binning_mod.ceil_pow2(db))
        span = max(span0, binning_mod.ceil_pow2(span))
        blk = binning_mod._pick_block_rows(da * db, lane_budget,
                                           binning_mod.DEFAULT_MAX_BLOCK_ROWS)
        if route == binning_mod.ROUTE_SPA:
            tile, _ = binning_mod.spa_tile(span, lane_budget)
            blk = int(max(1, min(blk, binning_mod.floor_pow2(
                max(1, lane_budget // tile)))))
            self.sigs[i] = (da, db, blk, route, tile, span)
        elif route == binning_mod.ROUTE_BIN:
            tile, ntiles = binning_mod.bin_tile(span, lane_budget)
            blk = int(max(1, min(blk, binning_mod.floor_pow2(
                max(1, lane_budget // (tile * ntiles))))))
            self.sigs[i] = (da, db, blk, route, tile, span)
        else:
            self.sigs[i] = (da, db, blk, route, 0, 0)
        self.growths += 1

    def assign(self, deg_a: np.ndarray, dbmax: np.ndarray,
               spans: np.ndarray | None) -> np.ndarray:
        """Row → bucket index under degree-bound dominance (first/narrowest
        dominating bucket wins; -1 when no bucket covers the row)."""
        m = deg_a.size
        out = np.full(m, -1, dtype=np.int32)
        for i, (da, db, _blk, route, _tile, span) in enumerate(self.sigs):
            ok = (out < 0) & (deg_a <= da) & (dbmax <= db)
            if (route in (binning_mod.ROUTE_SPA, binning_mod.ROUTE_BIN)
                    and spans is not None):
                ok &= spans <= span
            out[ok] = i
        return out

    def fit(self, a, b) -> "binning_mod.BinningPlan":
        """Assign every row of ``a·b`` to a template bucket, growing the
        template (monotone, pow2) where the member exceeds it, and return
        the member's :class:`~repro.core.binning.BinningPlan` carrying the
        template's static bounds."""
        if a.shape != self.shape_a or b.shape != self.shape_b:
            raise PlanMismatchError(
                f"member shapes {a.shape}/{b.shape} do not match template "
                f"{self.shape_a}/{self.shape_b}",
                observed=[list(a.shape), list(b.shape)],
                planned=[list(self.shape_a), list(self.shape_b)])
        a_rpt = np.asarray(a.rpt)
        a_col = np.asarray(a.col)
        b_rpt = np.asarray(b.rpt)
        rownnz_b = np.diff(b_rpt.astype(np.int64))
        deg_a, dbmax, _width = binning_mod.row_widths(a_rpt, a_col, rownnz_b)
        need_spans = any(s[3] in (binning_mod.ROUTE_SPA,
                                  binning_mod.ROUTE_BIN) for s in self.sigs)
        spans = (binning_mod.row_spans(a_rpt, a_col, b_rpt,
                                       np.asarray(b.col))
                 if need_spans else None)
        which = self.assign(deg_a, dbmax, spans)
        if (which < 0).any():
            # grow the widest bucket to cover the escapees, then re-assign
            left = which < 0
            self._grow_sig(len(self.sigs) - 1,
                           int(deg_a[left].max(initial=1)),
                           int(dbmax[left].max(initial=1)),
                           int(spans[left].max(initial=1))
                           if spans is not None else 1)
            which = self.assign(deg_a, dbmax, spans)
            assert (which >= 0).all()
        buckets = []
        row_bucket = np.zeros(deg_a.size, dtype=np.int32)
        for i, sig in enumerate(self.sigs):
            ids = np.ascontiguousarray(
                np.flatnonzero(which == i).astype(np.int32))
            da, db, blk, route, tile, span = sig
            n_tiles = (-(-binning_mod.ceil_pow2(max(1, span)) // tile)
                       if route in (binning_mod.ROUTE_SPA,
                                    binning_mod.ROUTE_BIN) and tile else 0)
            buckets.append(binning_mod.RowBucket(
                rows=ids, deg_a=da, deg_b=db, block_rows=blk, route=route,
                tile_n=tile, n_tiles=n_tiles, span=span))
            row_bucket[ids] = i
            if ids.size > self.pops[i]:
                self.pops[i] = binning_mod.ceil_pow2(ids.size)
                self.growths += 1
        gda = int(deg_a.max()) if deg_a.size else 1
        gdb = int(rownnz_b.max()) if rownnz_b.size else 1
        return binning_mod.BinningPlan(
            buckets=tuple(buckets), nrows=deg_a.size,
            global_deg_a=max(1, gda), global_deg_b=max(1, gdb),
            row_bucket=row_bucket)

    def grow_caps(self, member_caps) -> None:
        for i, c in enumerate(member_caps):
            if int(c) > self.caps[i]:
                self.caps[i] = binning_mod.ceil_pow2(int(c))
                self.growths += 1

    def dist_profile(self, num_shards: int) -> dict:
        """Per-mesh-size static shard profile: pow2 ``rows_pb`` and per-shard
        capacities per bucket, grown monotonically like the local half
        (first use seeds from the member without counting growth)."""
        if not hasattr(self, "_dist"):
            self._dist = {}
        return self._dist.setdefault(
            int(num_shards), dict(rows_pb=[0] * len(self.sigs),
                                  caps=[0] * len(self.sigs)))

    def grow_dist(self, num_shards: int, rows_pb, caps) -> tuple[list, list]:
        d = self.dist_profile(num_shards)
        fresh = not any(d["rows_pb"])
        for i, (r, c) in enumerate(zip(rows_pb, caps)):
            if int(r) > d["rows_pb"][i]:
                d["rows_pb"][i] = binning_mod.ceil_pow2(int(r))
                self.growths += 0 if fresh else 1
            if int(c) > d["caps"][i]:
                d["caps"][i] = binning_mod.ceil_pow2(int(c))
                self.growths += 0 if fresh else 1
        return list(d["rows_pb"]), list(d["caps"])

    def grow_device_caps(self, nnz_a: int, nnz_b: int) -> None:
        if nnz_a > self.cap_a:
            self.cap_a = _device_capacity(nnz_a)
            self.growths += 1
        if nnz_b > self.cap_b:
            self.cap_b = _device_capacity(nnz_b)
            self.growths += 1

    def stats(self) -> dict:
        return dict(buckets=len(self.sigs), sigs=[list(s) for s in self.sigs],
                    pops=list(self.pops), caps=list(self.caps),
                    cap_a=self.cap_a, cap_b=self.cap_b, growths=self.growths)


# --------------------------------------------------------------------------- #
# Automatic template selection — a session registry keyed on a cheap
# structural sketch, so callers get template-level executor sharing without
# holding the PlanTemplate handle (``plan_spgemm(template="auto")``).
# --------------------------------------------------------------------------- #
def _structural_sketch(a, b) -> tuple:
    """Cheap structural fingerprint of an operand pair: exact shapes plus a
    vector of log2 degree-regime statistics (mean/median gather width, mean
    A degree, mean referenced-B degree).

    The shapes match EXACTLY (templates require it); the statistics are
    matched with a tolerance by :class:`TemplateRegistry` — any hard
    quantization boundary would split a family whose seed-to-seed jitter
    straddles it, which is exactly the fragmentation templates exist to
    remove.  Genuinely different degree regimes differ by ≥ 1 in these
    log2 stats and never match at the default tolerance."""
    rownnz_b = np.diff(np.asarray(b.rpt, dtype=np.int64))
    deg_a, dbmax, width = binning_mod.row_widths(
        np.asarray(a.rpt), np.asarray(a.col), rownnz_b)
    if width.size:
        vec = (float(np.log2(max(1.0, width.mean()))),
               float(np.log2(max(1.0, np.median(width)))),
               float(np.log2(max(1.0, deg_a.mean()))),
               float(np.log2(1.0 + dbmax.mean())))
    else:
        vec = (0.0, 0.0, 0.0, 0.0)
    return (tuple(a.shape), tuple(b.shape)), vec


class TemplateRegistry:
    """Session-level structural-sketch → :class:`PlanTemplate` map.

    ``plan_spgemm(template="auto")`` resolves the member's sketch here: a
    hit plans against the family's existing template (growing it if the
    member exceeds it), a miss seeds a fresh template from the member's own
    quantized plan.  Matching is shape-exact and TOLERANT on the degree
    statistics (within ``tol`` in log2 space), so same-family different-seed
    members always resolve to one template even when a statistic sits on a
    quantization boundary.  Steady state is the §7 template contract —
    every member planned after the family's last growth shares one
    executor — reached without any caller coordinating template handles.
    """

    def __init__(self, tol: float = 0.75) -> None:
        self.tol = float(tol)
        self._families: dict = {}    # shapes → [(stats_vec, PlanTemplate)]
        self.hits = 0
        self.misses = 0

    def _match(self, shapes, vec) -> PlanTemplate | None:
        for ref, tpl in self._families.get(shapes, ()):
            if max(abs(x - y) for x, y in zip(vec, ref)) <= self.tol:
                return tpl
        return None

    def lookup(self, a, b) -> PlanTemplate | None:
        return self._match(*_structural_sketch(a, b))

    def get_or_create(self, a, b, build) -> PlanTemplate:
        # sketch ONCE per call — it is an O(nnz) host pass over A
        shapes, vec = _structural_sketch(a, b)
        tpl = self._match(shapes, vec)
        if tpl is None:
            self.misses += 1
            tpl = build()
            self._families.setdefault(shapes, []).append((vec, tpl))
        else:
            self.hits += 1
        return tpl

    def stats(self) -> dict:
        tpls = [t for fam in self._families.values() for _, t in fam]
        return dict(size=len(tpls), hits=self.hits, misses=self.misses,
                    growths=sum(t.growths for t in tpls))

    def clear(self) -> None:
        self._families.clear()
        self.hits = self.misses = 0


_DEFAULT_REGISTRY = TemplateRegistry()


def template_registry() -> TemplateRegistry:
    """The session-level default template registry."""
    return _DEFAULT_REGISTRY


# --------------------------------------------------------------------------- #
# Planning
# --------------------------------------------------------------------------- #
def _device_capacity(nnz: int) -> int:
    """pow2-padded device-CSR capacity: same-family matrices land on the
    same padded capacity, keeping the executor's traced shapes — and hence
    the plan cache — shared across them."""
    return binning_mod.ceil_pow2(max(8, int(nnz)))


def _mesh_key(mesh) -> tuple:
    if mesh is None:
        return ()
    return (tuple(mesh.axis_names),
            tuple(int(d.id) for d in np.asarray(mesh.devices).flat))


def _executor_key(plan: SpgemmPlan, mesh) -> tuple:
    return plan.key + (_mesh_key(mesh),)


def _build_shard_tables(binplan: binning_mod.BinningPlan,
                        partn: part_mod.Partition,
                        static_caps,
                        pow2_rows: bool = False,
                        rows_pb_list=None,
                        slices=None) -> tuple[BucketShardTable, ...]:
    bounds = np.asarray(partn.bounds)
    num_shards = partn.num_parts
    tables = []
    for i, (bucket, cap) in enumerate(zip(binplan.buckets, static_caps)):
        lo, hi = (slices[i] if slices is not None
                  else part_mod.shard_slices(bucket.rows, bounds))
        counts = hi - lo
        rows_pb = int(max(1, counts.max())) if counts.size else 1
        if pow2_rows:
            # population quantization: pad rows_pb so same-family
            # different-seed plans share the shard executor's traced shapes
            rows_pb = binning_mod.ceil_pow2(rows_pb)
        if rows_pb_list is not None:
            # template profile: the family's grown rows_pb dominates
            rows_pb = max(rows_pb, int(rows_pb_list[i]))
        table = np.empty((num_shards, rows_pb), dtype=np.int32)
        valid = np.zeros((num_shards, rows_pb), dtype=bool)
        for s in range(num_shards):
            ids = bucket.rows[lo[s]:hi[s]]
            n = ids.size
            if n:
                table[s, :n] = ids
                table[s, n:] = ids[-1]
            else:
                # shard owns no rows of this bucket: pad with any bucket row
                # (stays inside the bucket's degree envelope; discarded) —
                # row 0 for a bucket emptied under a template
                table[s, :] = bucket.rows[0] if bucket.n_rows else 0
            valid[s, :n] = True
        tables.append(BucketShardTable(table=table, valid=valid,
                                       capacity=int(cap)))
    return tuple(tables)


# --------------------------------------------------------------------------- #
# Column-partitioned B (DESIGN.md §8): panel slicing + the ragged gather that
# replaces full operand replication in the distributed numeric phase.
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class PanelGather:
    """Structure-only half of the panel-gathered numeric operands.

    Built ONCE at plan time from the bucket row tables (host, launch-time —
    the materialized form of the ragged all-to-all): device ``d = s·P + p``
    (row shard ``s``, panel ``p``) receives ONLY the panel-``p`` entries of
    the B rows shard ``s``'s A-rows actually reference, as a compact CSR of
    ``nref`` rows.  A's column indices are remapped per row shard into the
    compact row space, so the unmodified gather kernels
    (``csr.expand_products``) run against the gathered operand unchanged.

    Index arrays are seed-structure only and upload once per plan; the
    value payload (``g_idx`` → ``b.val``) is re-gathered per execute, which
    is what lets a revalued serving pair reuse every compiled executor.
    """

    nref: int               # compact referenced-row count (padded, pow2 opt)
    ecap: int               # gathered entries per (shard, panel) (padded)
    row_shards: int
    n_panels: int
    a_col: np.ndarray       # (row_shards, cap_a) int32 remapped A columns
                            # (a shard's panels share one row)
    g_rpt: np.ndarray       # (D, nref+1) int32 compact panel row pointers
    g_col: np.ndarray       # (D, ecap) int32 absolute columns, sentinel pad
    g_idx: np.ndarray       # (D, ecap) int64 → b.val entry index, -1 pad
    ref_nnz: np.ndarray     # (D,) int64 true gathered entries (payload)


def _slice_panels(b: CSR, edges: np.ndarray) -> tuple:
    """Split host B into column panels in ONE pass.

    Returns per panel ``(prpt, pcol, pidx)``: CSR row pointers over B's rows
    restricted to the panel, the (absolute) column ids, and each entry's
    index into ``b.col``/``b.val`` — the shared substrate of the symbolic
    phase (per-panel degree tables) AND the numeric gather (the §8 dedup:
    panels are sliced once, never per phase)."""
    col = np.asarray(b.col, dtype=np.int64)
    pid = np.searchsorted(np.asarray(edges, dtype=np.int64), col,
                          side="right") - 1
    rows_of = np.repeat(np.arange(b.nrows, dtype=np.int64), np.diff(b.rpt))
    out = []
    for p in range(len(edges) - 1):
        idx = np.flatnonzero(pid == p)
        prpt = np.zeros(b.nrows + 1, dtype=np.int64)
        if idx.size:
            np.cumsum(np.bincount(rows_of[idx], minlength=b.nrows),
                      out=prpt[1:])
        out.append((prpt, b.col[idx].astype(np.int32), idx))
    return tuple(out)


def _build_panel_gather(a: CSR, pslices, bounds, row_shards: int,
                        n_panels: int, cap_a: int,
                        pop_quant: bool) -> PanelGather:
    """Materialize the per-device gathered-B operands (host, launch-time).

    One referenced-row set per row shard (union over its buckets — shared by
    every bucket, every panel, both phases and the retry loop), one entry
    gather per (shard, panel)."""
    bounds = np.asarray(bounds, dtype=np.int64)
    nrows_b = pslices[0][0].size - 1
    a_rpt = np.asarray(a.rpt, dtype=np.int64)
    a_col_host = np.asarray(a.col, dtype=np.int64)
    nnz_a = int(a_rpt[-1])
    refs = []
    for s in range(row_shards):
        seg = a_col_host[a_rpt[bounds[s]]:a_rpt[bounds[s + 1]]]
        refs.append(np.unique(seg))
    nref = max(1, max((r.size for r in refs), default=1))
    if pop_quant:
        nref = binning_mod.ceil_pow2(nref)
    d_total = row_shards * n_panels
    # one remapped-A row per ROW SHARD — a shard's panels share it; the
    # per-device (D, cap_a) layout is materialized only at upload time
    # (np.repeat in _panel_dist_args), not retained host-side
    a_col = np.zeros((row_shards, cap_a), dtype=np.int32)
    panel_rows = [np.repeat(np.arange(nrows_b, dtype=np.int64),
                            np.diff(prpt)) for prpt, _, _ in pslices]
    sel_cols, sel_idx, sel_cnt = [], [], []
    for s in range(row_shards):
        remap = np.zeros(max(1, nrows_b), dtype=np.int64)
        remap[refs[s]] = np.arange(refs[s].size)
        in_ref = np.zeros(max(1, nrows_b), dtype=bool)
        in_ref[refs[s]] = True
        if nnz_a:
            a_col[s, :nnz_a] = remap[a_col_host].astype(np.int32)
        for p in range(n_panels):
            prpt, pcol, pidx = pslices[p]
            sel = np.flatnonzero(in_ref[panel_rows[p]])
            sel_cols.append(pcol[sel])
            sel_idx.append(pidx[sel])
            # compact row pointers: panel entries are CSR-ordered, refs are
            # ascending, so selected entries sort by compact row already
            sel_cnt.append(np.bincount(remap[panel_rows[p][sel]],
                                       minlength=nref))
    ecap = max(8, max((c.size for c in sel_cols), default=0))
    if pop_quant:
        ecap = binning_mod.ceil_pow2(ecap)
    # fault-injection hook (core.faults): no-op unless a test armed gather
    # starvation — an under-sized entry cap is DETECTED below, never written
    # past (the typed error replaces a silent out-of-bounds fill)
    ecap = faults_mod.scale_gather_cap(ecap)
    g_rpt = np.zeros((d_total, nref + 1), dtype=np.int32)
    g_col = np.full((d_total, ecap), COL_SENTINEL, dtype=np.int32)
    g_idx = np.full((d_total, ecap), -1, dtype=np.int64)
    ref_nnz = np.zeros(d_total, dtype=np.int64)
    for d in range(d_total):
        e = sel_cols[d].size
        if e > ecap:
            raise ShardFailureError(
                f"panel gather entry capacity {ecap} cannot hold the "
                f"{e} entries device {d} references",
                shard=d // n_panels, panel=d % n_panels,
                observed=int(e), planned=int(ecap))
        np.cumsum(sel_cnt[d], out=g_rpt[d, 1:])
        g_col[d, :e] = sel_cols[d]
        g_idx[d, :e] = sel_idx[d]
        ref_nnz[d] = e
    return PanelGather(nref=nref, ecap=ecap, row_shards=row_shards,
                       n_panels=n_panels, a_col=a_col, g_rpt=g_rpt,
                       g_col=g_col, g_idx=g_idx, ref_nnz=ref_nnz)


def _gather_panel_values(pg: PanelGather, b: CSR) -> np.ndarray:
    """The per-execute half of the ragged all-to-all: ship each device ONLY
    its gathered panel's value payload (``ecap`` floats, vs ``cap_b``
    replicated) — index arrays never move after planning."""
    bval = np.asarray(b.val, dtype=np.float32)
    safe = np.clip(pg.g_idx, 0, max(0, bval.size - 1))
    vals = bval[safe] if bval.size else np.zeros(pg.g_idx.shape, np.float32)
    return np.where(pg.g_idx >= 0, vals, np.float32(0.0))


def _panel_meta(bucket: binning_mod.RowBucket, db_p: int, cap: int,
                lane_budget: int = binning_mod.DEFAULT_LANE_BUDGET) -> tuple:
    """Bucket execution metadata at the PANEL deg_b bound: the gather buffer
    shrinks from ``deg_a·deg_b`` to ``deg_a·db_p`` lanes (a row's panel
    products are a subset of its full products), so ``block_rows`` re-fits
    the narrower width under the same VMEM budget.  Route/tile/span stay as
    planned — outputs are route-invariant (DESIGN.md §5)."""
    blk = binning_mod._pick_block_rows(bucket.deg_a * db_p, lane_budget,
                                       binning_mod.DEFAULT_MAX_BLOCK_ROWS)
    if bucket.route == binning_mod.ROUTE_SPA and bucket.tile_n:
        blk = int(max(1, min(blk, binning_mod.floor_pow2(
            max(1, lane_budget // bucket.tile_n)))))
    elif bucket.route == binning_mod.ROUTE_BIN and bucket.tile_n:
        blk = int(max(1, min(blk, binning_mod.floor_pow2(max(
            1, lane_budget // (bucket.tile_n * max(1, bucket.n_tiles)))))))
    return (bucket.deg_a, db_p, blk, bucket.route, bucket.tile_n,
            bucket.n_tiles, bucket.span, int(cap))


def plan_spgemm(a: CSR, b: CSR, *, mesh=None, num_shards: int | None = None,
                axis: str = "data", seed: int = 0, safety: float = 1.3,
                route: str = "auto", use_kernel: bool = False,
                sample_rows: np.ndarray | None = None,
                min_rows: int = binning_mod.DEFAULT_MIN_ROWS,
                deg_align: int = 1, pop_quant: bool = False,
                retry_safety: float = 0.0,
                max_retries: int = 4,
                retry_policy: "RetryPolicy | None" = None,
                dispatch_budget: "DispatchBudget | None" = None,
                validate: bool = True,
                template: "PlanTemplate | str | None" = None,
                registry: "TemplateRegistry | None" = None,
                n_panels: int = 0) -> SpgemmPlan:
    """Plan ``C = A·B``: sample → predict (binned, routed) → partition on
    predicted nnz → per-bucket(-per-shard) capacities.

    ``mesh``/``num_shards`` select distributed planning (``num_shards``
    alone plans without devices — useful for planning-time analysis; a mesh
    can then be supplied to :func:`execute`).  ``a``/``b`` are host ``CSR``;
    planning is a launch-time host step like ``core.partition``.

    ``pop_quant`` turns on plan-cache quantization: pow2-padded bucket
    populations / distributed ``rows_pb``, pow2 degree bounds and pow2
    capacities, so same-family different-seed matrices share executables at
    ≤2× row padding.  ``retry_safety`` > 0 arms the overflow re-planning
    loop in :func:`execute` (``×retry_safety^n`` pow2-rounded capacity bumps,
    only overflowing buckets re-execute, ≤ ``max_retries`` rounds).
    ``template`` (implies ``pop_quant``) plans against a
    :class:`PlanTemplate`'s frozen bucket ladder instead of the member's own
    width histogram — the strongest sharing: every member planned after the
    template's last growth lands on the SAME plan key.  Pass
    ``template="auto"`` to resolve the template from a
    :class:`TemplateRegistry` (default: the session registry) keyed on a
    cheap structural sketch — callers get steady-state executor reuse
    without holding the handle.

    ``n_panels`` > 0 selects **column-partitioned B** (DESIGN.md §8): B is
    split into ``n_panels`` contiguous column panels; the symbolic phase
    runs on per-panel degree tables and the numeric phase executes one
    (bucket × panel) unit at panel-bound buffer widths.  Distributed plans
    fold the panel axis onto the 1-D ``data`` axis — device ``d`` serves
    (row shard ``d // n_panels``, panel ``d % n_panels``) and receives ONLY
    the gathered panel entries its rows reference, replacing full B
    replication (``num_shards`` must be a multiple of ``n_panels``).

    ``use_kernel`` runs every bucket through the Pallas kernels; on a TPU
    backend it raises :class:`PlanMismatchError` before any dispatch, since
    that compiler refuses them (``kernels.ops.require_compilable``).
    """
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        kernel_ops.require_compilable()
    operands_validated = 0
    if validate:
        validate_mod.validate_pair(a, b)
        operands_validated = 2
    elif a.ncols != b.nrows:
        raise OperandValidationError(
            f"operand shapes {a.shape} and {b.shape} are incompatible "
            f"for A·B", observed=int(b.nrows), planned=int(a.ncols))
    if retry_policy is None and retry_safety > 0:
        # legacy knobs: the raw pair maps onto a ladder-only policy with the
        # pre-§9 surface-overflow behavior, so existing callers keep their
        # exact semantics
        retry_policy = RetryPolicy(rounds=int(max_retries),
                                   growth=float(retry_safety),
                                   exact_fallback=False,
                                   on_exhausted="surface")
    if isinstance(template, str):
        if template != "auto":
            raise PlanMismatchError(f"unknown template mode {template!r}")
        reg = registry if registry is not None else _DEFAULT_REGISTRY
        template = reg.get_or_create(a, b, lambda: PlanTemplate.from_plan(
            plan_spgemm(a, b, seed=seed, safety=safety, route=route,
                        use_kernel=use_kernel, sample_rows=sample_rows,
                        min_rows=min_rows, pop_quant=True)))
    if n_panels and (mesh is not None or num_shards):
        shards_chk = int(num_shards if num_shards else mesh.shape[axis])
        if shards_chk % int(n_panels):
            raise PlanMismatchError(
                f"n_panels={n_panels} must divide the mesh axis size "
                f"{shards_chk} (panels fold onto the data axis)",
                observed=int(shards_chk), planned=int(n_panels))
    if template is not None:
        pop_quant = True
        template.grow_device_caps(a.nnz, b.nnz)
        binplan = template.fit(a, b)
    else:
        if pop_quant and deg_align <= 1:
            # quantized plans need quantized degree bounds, or the per-bucket
            # signatures (exact degree maxima) would fragment the key anyway
            deg_align = binning_mod.POW2_DEG_ALIGN
        binplan = binning_mod.build_plan(a, b, route=route, min_rows=min_rows,
                                         deg_align=deg_align)
    flopr, total_flop = oracle.flop_per_row(a, b)
    if sample_rows is None:
        sample_rows = (oracle.sample_rows(a.nrows, seed) if a.nrows
                       else np.zeros(0, dtype=np.int64))
    sample_rows = np.asarray(sample_rows, dtype=np.int64)

    if template is not None:
        cap_a, cap_b = template.cap_a, template.cap_b
    else:
        cap_a = _device_capacity(a.nnz)
        cap_b = _device_capacity(b.nnz)
    devpair = None
    if total_flop > 0 and sample_rows.size:
        ad = csr_mod.to_device(a, capacity=cap_a)
        bd = csr_mod.to_device(b, capacity=cap_b)
        devpair = (ad, bd)
        pred = predictor_mod.proposed_predict_binned(
            ad, bd, jnp.asarray(sample_rows, dtype=jnp.int32), binplan,
            use_kernel=use_kernel, floprc=flopr)
        structure = np.asarray(pred.structure, dtype=np.float64)
        predicted_nnz = float(pred.nnz_total)
        cr = float(pred.compression_ratio)
        if not np.isfinite(structure).all() or cr <= 0:
            # sampled rows had no products (f* = 0): fall back to the
            # upper-bound structure — always safe, never over-allocates
            # past flopr by construction of the capacity rule.
            structure = flopr.astype(np.float64)
            predicted_nnz = float(total_flop)
            cr = 1.0
        # fault-injection hook (core.faults): no-op unless a test armed
        # sketch corruption — models an unlucky sample end to end
        structure, predicted_nnz, cr = faults_mod.corrupt_sketch(
            structure, predicted_nnz, cr)
    else:
        structure = np.zeros(a.nrows, dtype=np.float64)
        predicted_nnz = 0.0
        cr = 1.0

    alloc = predictor_mod.BinnedAllocationPlan.from_prediction(
        binplan, structure, flopr, safety=safety, pow2=pop_quant)
    if template is not None:
        # the family's grown capacities dominate the member's prediction
        template.grow_caps(alloc.bucket_capacities)
        caps = tuple(template.caps)
        alloc = predictor_mod.BinnedAllocationPlan(
            bucket_capacities=caps,
            row_capacity=max(caps) if caps else 8,
            total_capacity=sum(bk.n_rows * c
                               for bk, c in zip(binplan.buckets, caps)),
            safety=safety)

    plan = SpgemmPlan(
        binning=binplan, alloc=alloc, structure=structure, flopr=flopr,
        predicted_nnz=predicted_nnz, compression_ratio=cr,
        sample_rows=sample_rows, shape_a=a.shape, shape_b=b.shape,
        cap_a=cap_a, cap_b=cap_b, safety=safety, use_kernel=use_kernel,
        pop_quant=pop_quant,
        retry_safety=(retry_policy.growth if retry_policy is not None
                      else retry_safety),
        max_retries=(retry_policy.rounds if retry_policy is not None
                     else max_retries),
        retry_policy=retry_policy,
        dispatch_budget=dispatch_budget)
    plan.validation["operands_validated"] = operands_validated
    if template is not None:
        plan._template = template
        plan._pop_override = tuple(template.pops)
    if devpair is not None:
        if n_panels:
            # panel executes never touch a replicated device B — keeping the
            # prediction pass's upload would pin cap_b·8 bytes per plan, the
            # very replication §8 removes.  Drop it; keep A's upload and the
            # HOST references (they gate the structure-fingerprint check).
            plan._planned_pair = ((a, b), (devpair[0], None))
        else:
            plan._planned_pair = ((a, b), devpair)

    structure_p = flopr_p = None
    if n_panels:
        # -- column panels (§8): slice B once; per-panel degree tables feed
        # both the symbolic capacities and the numeric gather (the dedup) --
        panels = part_mod.column_panels(b, int(n_panels), quantize=pop_quant)
        pslices = _slice_panels(b, panels.edges)
        dbmax_p, flopr_p = binning_mod.panel_row_tables(
            a.rpt, a.col, [ps[0] for ps in pslices])
        # per-panel predicted structure: eq. 4 applied per panel with the
        # plan's sampled r* (flopr partitions exactly over panels, so the
        # panel predictions sum to the full-row prediction)
        structure_p = flopr_p.astype(np.float64) / max(float(cr), 1e-9)
        dbrow = dbmax_p.max(axis=0) if dbmax_p.size else np.zeros(0, np.int64)
        panel_align = binning_mod.POW2_DEG_ALIGN if pop_quant else deg_align
        plan.n_panels = int(n_panels)
        plan.panels = panels
        plan.panel_deg_b = tuple(
            binning_mod.round_deg(
                int(dbrow[bk.rows].max()) if bk.n_rows else 1, panel_align)
            for bk in binplan.buckets)
        plan._panel_host = pslices
        plan._nnz_b = int(b.nnz)
        plan._panel_a_fp = (int(a.nnz),
                            int(np.asarray(a.col, dtype=np.int64).sum()))
        plan._panel_b_fp = (int(b.nnz),
                            int(np.asarray(b.col, dtype=np.int64).sum()))

    if mesh is not None or num_shards:
        shards = int(num_shards if num_shards else mesh.shape[axis])
        row_shards = shards // int(n_panels) if n_panels else shards
        partn = part_mod.balanced_contiguous(structure, row_shards)
        caps_mat, static_caps = predictor_mod.shard_bucket_capacities(
            binplan, structure, flopr, partn.bounds, safety=safety,
            pow2=pop_quant, panel_structure=structure_p,
            panel_flopr=flopr_p)
        rows_pb_list = slices = None
        if template is not None:
            # member per-bucket rows_pb (pow2) → grow the family profile,
            # then pad every table to the grown profile (the shard slices
            # are computed once and reused for the table fill)
            slices = [part_mod.shard_slices(bucket.rows, partn.bounds)
                      for bucket in binplan.buckets]
            member_pb = []
            for lo, hi in slices:
                counts = hi - lo
                member_pb.append(binning_mod.ceil_pow2(
                    int(max(1, counts.max())) if counts.size else 1))
            rows_pb_list, static_caps = template.grow_dist(
                row_shards, member_pb, static_caps)
        plan.num_shards = shards
        plan.axis = axis
        plan.partition = partn
        tables = _build_shard_tables(binplan, partn, static_caps,
                                     pow2_rows=pop_quant,
                                     rows_pb_list=rows_pb_list,
                                     slices=slices)
        if n_panels:
            # fold the panel axis onto the data axis: device d = s·P + p
            # repeats row shard s's table for each of its P panels
            tables = tuple(BucketShardTable(
                table=np.repeat(t.table, int(n_panels), axis=0),
                valid=np.repeat(t.valid, int(n_panels), axis=0),
                capacity=t.capacity) for t in tables)
            plan.row_shards = row_shards
            plan.panel_caps = np.tile(
                np.asarray(static_caps, dtype=np.int64)[:, None],
                (1, int(n_panels)))
            plan._panel_gather = _build_panel_gather(
                a, pslices, partn.bounds, row_shards, int(n_panels), cap_a,
                pop_quant)
        plan.shard_tables = tables
        plan.shard_capacities = caps_mat
        plan.mesh = mesh
    elif n_panels:
        # single-device panel mode: per-(bucket, panel) capacities are the
        # executor statics (each unit runs standalone, no SPMD coupling)
        pc_mat, _ = predictor_mod.shard_bucket_capacities(
            binplan, structure, flopr, np.array([0, a.nrows]), safety=safety,
            panel_structure=structure_p, panel_flopr=flopr_p)
        pc = np.maximum(8, pc_mat[:, 0, :])
        if pop_quant:  # plain loop: np.vectorize dies on zero-bucket plans
            pc = np.array([[binning_mod.ceil_pow2(int(c)) for c in row]
                           for row in pc], dtype=np.int64).reshape(pc.shape)
        plan.panel_caps = pc.astype(np.int64)
        plan._panel_caps_dev = tuple(
            faults_mod.scale_gather_cap(_device_capacity(int(n)))
            for n in panels.panel_nnz)
    return plan


# --------------------------------------------------------------------------- #
# Executors (cache-built, trace-counted)
# --------------------------------------------------------------------------- #
def _bucket_meta(bucket: binning_mod.RowBucket, cap: int) -> tuple:
    """Hashable static execution metadata for one bucket."""
    return (bucket.deg_a, bucket.deg_b, bucket.block_rows, bucket.route,
            bucket.tile_n, bucket.n_tiles, bucket.span, int(cap))


def _run_bucket(ad: CSRDevice, bd: CSRDevice, rows: jax.Array, meta: tuple,
                use_kernel: bool) -> SpGEMMOut:
    deg_a, deg_b, block_rows, route, tile_n, n_tiles, span, cap = meta
    return routed_spgemm_rows(
        ad, bd, rows, row_capacity=cap, deg_a=deg_a, deg_b=deg_b,
        block_rows=block_rows, route=route, tile_n=tile_n, n_tiles=n_tiles,
        span=span, use_kernel=use_kernel)


def _build_local_executor(metas: tuple, cap_out: int, use_kernel: bool,
                          cache: PlanCache, masked: bool = False):
    """Single-device executor: per-bucket routed passes + one concat/perm
    assembly — the :func:`repro.core.spgemm.spgemm_binned` dataflow inside
    one cached jit (row ids and the inverse permutation stay traced so the
    compiled program serves every same-keyed plan).

    ``masked`` is the pop-quant variant: bucket tables arrive pow2-padded
    with validity masks; pad rows (repeat-last fill) are excluded from the
    overflow count and never selected by the padded-layout ``perm``.
    """
    nb = len(metas)

    @jax.jit
    def run(ad, bd, perm, *rest):
        cache._note_trace()
        masks = rest[:nb] if masked else (None,) * nb
        tables = rest[nb:] if masked else rest
        parts_c, parts_v, parts_n = [], [], []
        overflow = jnp.int32(0)
        for meta, rows, mask in zip(metas, tables, masks):
            c, v, n, of = _run_bucket(ad, bd, rows, meta, use_kernel)
            if masked:
                of = jnp.where(mask, jnp.maximum(n - meta[-1], 0), 0).sum()
            c, v = pad_to_capacity(c, v, cap_out)
            parts_c.append(c)
            parts_v.append(v)
            parts_n.append(n.astype(jnp.int32))
            overflow = overflow + of.astype(jnp.int32)
        return SpGEMMOut(jnp.concatenate(parts_c, axis=0)[perm],
                         jnp.concatenate(parts_v, axis=0)[perm],
                         jnp.concatenate(parts_n, axis=0)[perm],
                         overflow)

    return run


def _build_bucket_executor(meta: tuple, use_kernel: bool, cache: PlanCache):
    """One bucket's standalone executor — the re-planning loop's unit of
    re-execution (trace-counted like the full executors)."""

    @jax.jit
    def run(ad, bd, rows):
        cache._note_trace()
        return _run_bucket(ad, bd, rows, meta, use_kernel)

    return run


def _build_bucket_dist_executor(meta: tuple, mesh, axis: str,
                                use_kernel: bool, cache: PlanCache):
    """One bucket's shard_map executor — the distributed re-planning unit."""

    def shard_fn(ad, bd, table):
        cache._note_trace()
        c, v, n, _ = _run_bucket(ad, bd, table[0], meta, use_kernel)
        return c[None], v[None], n.astype(jnp.int32)[None]

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(), P(), P(axis, None)),
                       out_specs=(P(axis, None, None), P(axis, None, None),
                                  P(axis, None)),
                       check_vma=False)
    return jax.jit(fn)


def _build_dist_executor(metas: tuple, mesh, axis: str, use_kernel: bool,
                         cache: PlanCache):
    """shard_map executor: every shard runs every bucket's routed pass over
    its own row table — the binned/routed backend at pod scale.  A/B are
    replicated (index/value arrays broadcast once, as in the legacy path);
    only the row tables are sharded.  Per-shard overflow is derived host-
    side from the returned true ``row_nnz`` and the plan's valid masks."""

    def shard_fn(ad, bd, *tables):
        cache._note_trace()
        outs = []
        for meta, table in zip(metas, tables):
            c, v, n, _ = _run_bucket(ad, bd, table[0], meta, use_kernel)
            outs.extend([c[None], v[None], n.astype(jnp.int32)[None]])
        return tuple(outs)

    nb = len(metas)
    in_specs = (P(), P()) + (P(axis, None),) * nb
    out_specs = tuple(s for _ in range(nb)
                      for s in (P(axis, None, None), P(axis, None, None),
                                P(axis, None)))
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _build_local_panel_executor(metas: tuple, use_kernel: bool,
                                cache: PlanCache, masked: bool = False):
    """Single-device panel executor: one routed pass per (bucket × panel),
    each at its own panel-bound gather width and its own per-panel capacity.
    Panels partition the column space, so no merge pass follows — the
    per-(bucket, panel) blocks ARE the output (:class:`PanelSpgemmOut`)."""
    nb = len(metas)

    @jax.jit
    def run(ad, bps, *rest):
        cache._note_trace()
        masks = rest[:nb] if masked else (None,) * nb
        tables = rest[nb:] if masked else rest
        cols, vals, nnzs = [], [], []
        overflow = jnp.int32(0)
        for pmetas, rows, mask in zip(metas, tables, masks):
            bc, bv, bn = [], [], []
            for bp, meta in zip(bps, pmetas):
                c, v, n, of = _run_bucket(ad, bp, rows, meta, use_kernel)
                if masked:
                    of = jnp.where(mask, jnp.maximum(n - meta[-1], 0), 0).sum()
                bc.append(c)
                bv.append(v)
                bn.append(n.astype(jnp.int32))
                overflow = overflow + of.astype(jnp.int32)
            cols.append(tuple(bc))
            vals.append(tuple(bv))
            nnzs.append(tuple(bn))
        return PanelSpgemmOut(tuple(cols), tuple(vals), tuple(nnzs), overflow)

    return run


def _build_panel_dist_executor(metas: tuple, shape_a, nref: int, ncols_b: int,
                               mesh, axis: str, use_kernel: bool,
                               cache: PlanCache):
    """shard_map executor for column-partitioned B (DESIGN.md §8).

    Device ``d = s·P + p`` runs row shard ``s``'s bucket tables against its
    GATHERED panel operand — a compact CSR of only the B rows shard ``s``
    references, panel ``p`` entries only — through the same routed per-bucket
    dispatch as every other executor.  A's value/rpt arrays stay replicated;
    A's column indices arrive remapped per device into the compact row
    space.  Nothing else in the kernel stack changes: ``expand_products``
    cannot tell a gathered panel from a full operand."""

    def shard_fn(a_rpt, a_val, a_col, g_rpt, g_col, g_val, *tables):
        cache._note_trace()
        ad = CSRDevice(rpt=a_rpt, col=a_col[0], val=a_val,
                       shape=tuple(shape_a))
        bd = CSRDevice(rpt=g_rpt[0], col=g_col[0], val=g_val[0],
                       shape=(nref, ncols_b))
        outs = []
        for meta, table in zip(metas, tables):
            c, v, n, _ = _run_bucket(ad, bd, table[0], meta, use_kernel)
            outs.extend([c[None], v[None], n.astype(jnp.int32)[None]])
        return tuple(outs)

    nb = len(metas)
    in_specs = (P(), P(), P(axis, None), P(axis, None), P(axis, None),
                P(axis, None)) + (P(axis, None),) * nb
    out_specs = tuple(s for _ in range(nb)
                      for s in (P(axis, None, None), P(axis, None, None),
                                P(axis, None)))
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def _panel_operands_local(plan: SpgemmPlan, b: CSR) -> list:
    """Per-panel device CSRs at the plan's padded panel capacities.

    Structure (rpt + padded col) is seed-structure only: built and uploaded
    ONCE per plan (cached in ``_panel_dev``, the local twin of
    :func:`_panel_dist_args`); only the value payload re-gathers from ``b``
    per execute — the serving pair reuses executors AND index uploads."""
    if plan._panel_dev is None:
        structs = []
        for p, ((prpt, pcol, _), cap) in enumerate(
                zip(plan._panel_host, plan._panel_caps_dev)):
            if pcol.size > cap:
                raise CapacityExhaustedError(
                    f"panel {p} operand capacity {cap} cannot hold its "
                    f"{pcol.size} entries", panel=p,
                    observed=int(pcol.size), planned=int(cap),
                    plan_key=_plan_key_id(plan))
            col = np.full(cap, COL_SENTINEL, dtype=np.int32)
            col[:pcol.size] = pcol
            structs.append((jnp.asarray(prpt, dtype=jnp.int32),
                            jnp.asarray(col)))
        plan._panel_dev = tuple(structs)
    out = []
    bval = np.asarray(b.val, dtype=np.float32)
    for (rpt_d, col_d), (_, pcol, pidx), cap in zip(plan._panel_dev,
                                                    plan._panel_host,
                                                    plan._panel_caps_dev):
        val = np.zeros(cap, dtype=np.float32)
        val[:pcol.size] = bval[pidx]
        out.append(CSRDevice(rpt=rpt_d, col=col_d, val=jnp.asarray(val),
                             shape=b.shape))
    return out


def _panel_dist_args(plan: SpgemmPlan) -> tuple:
    """Structure-only device uploads of the panel gather (once per plan)."""
    if plan._panel_dev is None:
        pg = plan._panel_gather
        plan._panel_dev = (
            jnp.asarray(np.repeat(pg.a_col, pg.n_panels, axis=0)),
            jnp.asarray(pg.g_rpt), jnp.asarray(pg.g_col))
    return plan._panel_dev


def _check_panel_operand(plan: SpgemmPlan, m, which: str = "b") -> CSR:
    """Panel plans bake operand STRUCTURE into the gather maps (B's entry
    indices; distributed, also A's remapped columns), so a same-shape
    different-structure operand would silently produce a wrong matrix.
    Require the host CSR and match its (nnz, col-sum) fingerprint against
    the planned operand's."""
    shape = plan.shape_b if which == "b" else plan.shape_a
    fp = plan._panel_b_fp if which == "b" else plan._panel_a_fp
    plan.validation["fingerprint_checks"] += 1
    if not isinstance(m, CSR):
        raise PlanMismatchError(
            f"panel plans bake operand {which}'s structure into the gather "
            "maps — pass the host CSR operand, not a CSRDevice",
            operand=which, plan_key=_plan_key_id(plan))
    m_fp = (int(m.nnz), int(np.asarray(m.col, dtype=np.int64).sum()))
    if m.shape != shape or m_fp != fp:
        raise PlanMismatchError(
            f"operand {which} shape/structure {m.shape}/nnz={m.nnz} does "
            f"not match the planned operand ({shape}/nnz={fp[0]}) — the "
            "panel gather map is structure-specific; re-plan for a new "
            "sparsity pattern", operand=which, observed=list(m_fp),
            planned=list(fp), plan_key=_plan_key_id(plan))
    return m


def _coerce_one(plan: SpgemmPlan, m, which: str, idx: int) -> CSRDevice:
    cap = plan.cap_a if which == "a" else plan.cap_b
    shape = plan.shape_a if which == "a" else plan.shape_b
    if isinstance(m, CSRDevice):
        # a pre-converted operand must sit at the plan's padded
        # capacity, or the cached executor would silently retrace per
        # distinct nnz (voiding the zero-retrace serving contract) —
        # or worse, compute a different matrix without complaint
        if m.shape != shape or m.capacity != cap:
            raise PlanMismatchError(
                f"operand {which}: CSRDevice shape/capacity "
                f"{m.shape}/{m.capacity} does not match the plan's "
                f"{shape}/{cap} — convert with plan.to_device()",
                operand=which, observed=[list(m.shape), int(m.capacity)],
                planned=[list(shape), int(cap)],
                plan_key=_plan_key_id(plan))
        return m
    if plan._planned_pair is not None and m is plan._planned_pair[0][idx]:
        return plan._planned_pair[1][idx]
    return plan.to_device(m, which)


def _coerce_pair(plan: SpgemmPlan, a, b) -> tuple[CSRDevice, CSRDevice]:
    return _coerce_one(plan, a, "a", 0), _coerce_one(plan, b, "b", 1)


# --------------------------------------------------------------------------- #
# Overflow re-planning (DESIGN.md §7) + retry escalation (§9): bump ONLY the
# overflowing buckets' capacities and re-execute them — the realloc half of
# the paper's story; when the ladder exhausts, escalate once to an exact
# symbolic count for the offending units.
# --------------------------------------------------------------------------- #
def _bumped_capacity(cap: int, need: int, retry_safety: float,
                     attempt: int) -> int:
    """Safety-factor schedule ``×retry_safety^attempt``, floored at the
    observed need (``row_nnz`` is exact, so one round converges) and
    pow2-rounded so retry capacities stay cache-quantized."""
    sched = int(np.ceil(cap * (retry_safety ** attempt)))
    return binning_mod.ceil_pow2(max(need, sched, cap + 1))


def _policy_of(plan: SpgemmPlan) -> RetryPolicy:
    """The plan's escalation policy (legacy ``retry_safety``/``max_retries``
    fields resolve to a ladder-only, surface-overflow policy)."""
    if plan.retry_policy is not None:
        return plan.retry_policy
    return RetryPolicy(rounds=int(plan.max_retries),
                       growth=float(plan.retry_safety) or 1.5,
                       exact_fallback=False, on_exhausted="surface")


def _exact_capacity(need: int, cap: int) -> int:
    """Guaranteed-sufficient pow2 capacity for the exact-symbolic fallback
    (never below the current cap — splicing only widens buffers)."""
    return binning_mod.ceil_pow2(max(8, int(need), int(cap)))


def _unit_priced_seconds(meta: tuple, rows: int) -> float:
    """Expected seconds of one (bucket[×panel]) unit dispatch, priced from
    its execution metadata: ``width = deg_a·deg_b`` products per row over
    ``rows`` dispatched rows (pads included — they cost real lanes)."""
    deg_a, deg_b = int(meta[0]), int(meta[1])
    return profiles_mod.unit_seconds(meta[3], deg_a * max(1, deg_b),
                                     int(meta[6]), int(rows))


def _plan_priced_seconds(plan: SpgemmPlan) -> float:
    """Expected seconds of one full execute() wave — the sum over every
    (bucket × panel[× shard]) unit the wave dispatches.  Feeds
    :class:`DispatchBudget` for the fused wave; recovery prices each unit
    individually with :func:`_unit_priced_seconds`."""
    total = 0.0
    if plan.distributed:
        for i, (bk, t) in enumerate(zip(plan.binning.buckets,
                                        plan.shard_tables)):
            if plan.n_panels:
                meta = _panel_meta(bk, plan.panel_deg_b[i], t.capacity)
            else:
                meta = _bucket_meta(bk, t.capacity)
            # SPMD wave: every device runs its rows_pb slice concurrently,
            # so the wave's critical path is ONE shard's unit per bucket
            total += _unit_priced_seconds(meta, t.rows_pb)
        return total
    pops = plan.local_populations()
    for i, (bk, pop) in enumerate(zip(plan.binning.buckets, pops)):
        if plan.n_panels:
            for p in range(plan.n_panels):
                meta = _panel_meta(bk, plan.panel_deg_b[i],
                                   int(plan.panel_caps[i, p]))
                total += _unit_priced_seconds(meta, pop)
        else:
            meta = _bucket_meta(bk, int(plan.alloc.bucket_capacities[i]))
            total += _unit_priced_seconds(meta, pop)
    return total


def _invoke_executor(run, info: dict, *args, budget: "DispatchBudget | None"
                     = None, priced_s: float = 0.0,
                     cache: PlanCache | None = None):
    """Every executor dispatch funnels here: the fault-injection hook
    (``core.faults.check_executor``) fires pre-dispatch, and any exception
    out of the executor — injected or real — surfaces as a typed
    :class:`ShardFailureError` naming the dispatch unit instead of an
    anonymous traceback from inside a jitted program.

    When ``budget`` is armed (``plan.dispatch_budget``), the dispatch is
    synchronized (``jax.block_until_ready``) and timed against
    ``budget.limit(priced_s)``; exceeding it raises a typed
    :class:`StragglerError` carrying observed vs planned seconds.  Real
    wall time only counts for dispatches that did NOT retrace (``cache``
    detects tracing — compile time is not a straggler); injected
    ``delay_executor`` seconds always count."""
    try:
        faults_mod.check_executor(info)
        if budget is None:
            return run(*args)
        t0 = time.perf_counter()
        traces0 = cache.traces if cache is not None else None
        out = jax.block_until_ready(run(*args))
        elapsed = time.perf_counter() - t0
        if traces0 is not None and cache.traces > traces0:
            elapsed = 0.0              # first dispatch: compiling, exempt
        elapsed += faults_mod.executor_delay(info)
        limit = budget.limit(priced_s)
        if elapsed > limit:
            raise StragglerError(
                f"dispatch exceeded its budget: {elapsed:.4f}s > "
                f"{limit:.4f}s", observed=round(elapsed, 6),
                planned=round(limit, 6), **info)
        return out
    except SpgemmError:
        raise
    except Exception as e:
        raise ShardFailureError(f"executor failed: {e}", **info) from e


def _replan_local(plan: SpgemmPlan, ad, bd, out: SpGEMMOut,
                  cache: PlanCache) -> SpGEMMOut:
    policy = _policy_of(plan)
    buckets = plan.binning.buckets
    caps = list(plan.alloc.bucket_capacities)
    n = np.asarray(out.row_nnz, dtype=np.int64)
    col = val = None                   # materialized on first splice only
    args = plan.device_args()
    tables = args[1 + len(buckets):] if plan.pop_quant else args[1:]
    plan.retries = 0
    plan.retry_events = []             # observability covers the LAST execute
    plan.degradations = []

    def splice(i, new_cap, c2, v2):
        nonlocal col, val
        bk = buckets[i]
        c2 = np.asarray(c2)[:bk.n_rows]
        v2 = np.asarray(v2)[:bk.n_rows]
        if new_cap > col.shape[1]:
            grow = new_cap - col.shape[1]
            col = np.concatenate(
                [col, np.full((col.shape[0], grow), COL_SENTINEL,
                              np.int32)], axis=1)
            val = np.concatenate(
                [val, np.zeros((val.shape[0], grow), np.float32)], axis=1)
        col[bk.rows, :new_cap] = c2
        val[bk.rows, :new_cap] = v2

    def rerun(i, new_cap, unit):
        bk = buckets[i]
        meta = _bucket_meta(bk, new_cap)
        pop = int(tables[i].shape[0])
        run = cache.executor(
            ("bucket-retry", plan.shape_a, plan.shape_b, plan.cap_a,
             plan.cap_b, plan.use_kernel, meta, pop),
            lambda m=meta: _build_bucket_executor(m, plan.use_kernel,
                                                 cache))
        c2, v2, _, _ = _invoke_executor(run, dict(unit=unit, bucket=i),
                                        ad, bd, tables[i])
        splice(i, new_cap, c2, v2)

    for attempt in range(1, policy.rounds + 1):
        bumps = []
        for i, bk in enumerate(buckets):
            if not bk.n_rows:
                continue
            need = int(n[bk.rows].max())
            if need <= caps[i]:
                continue
            new_cap = policy.clamp(
                caps[i], _bumped_capacity(caps[i], need, policy.growth,
                                          attempt))
            if new_cap > caps[i]:      # ceiling-clamped units wait for the
                bumps.append((i, need, new_cap))   # exact fallback instead
        if not bumps:
            break
        if col is None:
            col = np.asarray(out.col).copy()
            val = np.asarray(out.val).copy()
        plan.retries = attempt
        for i, need, new_cap in bumps:
            rerun(i, new_cap, "bucket-retry")
            plan.retry_events.append(dict(
                round=attempt, bucket=i, old_cap=caps[i], new_cap=new_cap,
                need=need))
            caps[i] = new_cap
    # ladder exhausted (no rounds left, or every bump ceiling-clamped):
    # escalate ONCE to an exact symbolic count for the offending buckets —
    # guaranteed-sufficient caps, bitwise-correct output (DESIGN.md §9)
    over = [i for i, bk in enumerate(buckets)
            if bk.n_rows and int(n[bk.rows].max()) > caps[i]]
    if over and policy.exact_fallback:
        if col is None:
            col = np.asarray(out.col).copy()
            val = np.asarray(out.val).copy()
        for i in over:
            bk = buckets[i]
            counts = predictor_mod.exact_row_counts(
                ad, bd, bk.rows, max_deg_a=bk.deg_a, max_deg_b=bk.deg_b,
                route=bk.route, span=bk.span)
            need = int(counts.max(initial=1))
            new_cap = _exact_capacity(need, caps[i] + 1)
            rerun(i, new_cap, "exact-fallback")
            plan.degradations.append(dict(
                kind="exact_symbolic", bucket=i, old_cap=int(caps[i]),
                new_cap=int(new_cap), need=int(need)))
            caps[i] = new_cap
    if col is None:
        if over and policy.on_exhausted == "raise":
            raise CapacityExhaustedError(
                f"retry escalation exhausted with {int(out.overflow)} "
                f"entries still dropped (buckets {over})", buckets=over,
                observed=int(out.overflow),
                planned=[int(caps[i]) for i in over],
                plan_key=_plan_key_id(plan))
        return out                     # fast path: nothing overflowed
    # final capacities + overflow recomputed against the bumped plan
    capv = np.zeros(n.shape[0], dtype=np.int64)
    for bk, cap in zip(buckets, caps):
        capv[bk.rows] = cap
    overflow = int(np.maximum(n - capv, 0).sum())
    plan.alloc = predictor_mod.BinnedAllocationPlan(
        bucket_capacities=tuple(caps), row_capacity=max(caps),
        total_capacity=sum(bk.n_rows * c for bk, c in zip(buckets, caps)),
        safety=plan.alloc.safety)
    if plan._template is not None:
        plan._template.grow_caps(caps)   # the family learns from the miss
    if overflow and policy.on_exhausted == "raise":
        bad = [i for i, bk in enumerate(buckets)
               if bk.n_rows and int(n[bk.rows].max()) > caps[i]]
        raise CapacityExhaustedError(
            f"retry escalation exhausted with {overflow} entries still "
            f"dropped (buckets {bad})", buckets=bad, observed=int(overflow),
            planned=[int(caps[i]) for i in bad], plan_key=_plan_key_id(plan))
    return SpGEMMOut(jnp.asarray(col), jnp.asarray(val), out.row_nnz,
                     jnp.int32(overflow))


def _replan_dist(plan: SpgemmPlan, ad, bd, out: DistSpgemmOut,
                 cache: PlanCache, mesh) -> DistSpgemmOut:
    policy = _policy_of(plan)
    buckets = plan.binning.buckets
    tables = list(plan.shard_tables)
    nnzs = [np.asarray(x, dtype=np.int64) for x in out.row_nnz]
    cols, vals = list(out.cols), list(out.vals)
    args = plan.device_args()
    plan.retries = 0
    plan.retry_events = []             # observability covers the LAST execute
    plan.degradations = []
    changed = False

    def rerun(i, new_cap, unit):
        t = tables[i]
        meta = _bucket_meta(buckets[i], new_cap)
        run = cache.executor(
            ("bucket-retry-dist", plan.shape_a, plan.shape_b, plan.cap_a,
             plan.cap_b, plan.use_kernel, meta, t.rows_pb, plan.axis,
             _mesh_key(mesh)),
            lambda m=meta: _build_bucket_dist_executor(
                m, mesh, plan.axis, plan.use_kernel, cache))
        c2, v2, _ = _invoke_executor(run, dict(unit=unit, bucket=i),
                                     ad, bd, args[i])
        cols[i], vals[i] = c2, v2
        tables[i] = dataclasses.replace(t, capacity=new_cap)

    for attempt in range(1, policy.rounds + 1):
        bumps = []
        for i, t in enumerate(tables):
            need = int(np.where(t.valid, nnzs[i], 0).max(initial=0))
            if need <= t.capacity:
                continue
            new_cap = policy.clamp(
                t.capacity, _bumped_capacity(t.capacity, need, policy.growth,
                                             attempt))
            if new_cap > t.capacity:
                bumps.append((i, need, new_cap))
        if not bumps:
            break
        plan.retries = attempt
        changed = True
        for i, need, new_cap in bumps:
            old_cap = tables[i].capacity
            rerun(i, new_cap, "bucket-retry")
            plan.retry_events.append(dict(
                round=attempt, bucket=i, old_cap=old_cap,
                new_cap=new_cap, need=need))
    # exact-symbolic escalation for units the ladder could not cover (§9)
    over = [i for i, t in enumerate(tables)
            if int(np.where(t.valid, nnzs[i], 0).max(initial=0)) > t.capacity]
    if over and policy.exact_fallback:
        changed = True
        for i in over:
            bk = buckets[i]
            counts = predictor_mod.exact_row_counts(
                ad, bd, bk.rows, max_deg_a=bk.deg_a, max_deg_b=bk.deg_b,
                route=bk.route, span=bk.span)
            need = int(counts.max(initial=1))
            old_cap = tables[i].capacity
            new_cap = _exact_capacity(need, old_cap + 1)
            rerun(i, new_cap, "exact-fallback")
            plan.degradations.append(dict(
                kind="exact_symbolic", bucket=i, old_cap=int(old_cap),
                new_cap=int(new_cap), need=int(need)))
    if not changed:
        if over and policy.on_exhausted == "raise":
            shards = [int(s) for s in
                      np.flatnonzero(np.asarray(out.shard_overflow))]
            raise ShardFailureError(
                f"retry escalation exhausted with "
                f"{int(np.asarray(out.shard_overflow).sum())} entries still "
                f"dropped on shards {shards}", shards=shards, buckets=over,
                observed=int(np.asarray(out.shard_overflow).sum()),
                plan_key=_plan_key_id(plan))
        return out                     # fast path: nothing overflowed
    plan.shard_tables = tuple(tables)  # reassemble reads the final widths
    if plan._template is not None:
        plan._template.grow_dist(plan.num_shards,
                                 [t.rows_pb for t in tables],
                                 [t.capacity for t in tables])
    overflow = np.zeros(plan.num_shards, dtype=np.int64)
    for t, n in zip(tables, nnzs):
        overflow += np.where(t.valid,
                             np.maximum(n - t.capacity, 0), 0).sum(axis=1)
    if overflow.sum() and policy.on_exhausted == "raise":
        shards = [int(s) for s in np.flatnonzero(overflow)]
        raise ShardFailureError(
            f"retry escalation exhausted with {int(overflow.sum())} entries "
            f"still dropped on shards {shards}", shards=shards,
            observed=int(overflow.sum()), plan_key=_plan_key_id(plan))
    return DistSpgemmOut(tuple(cols), tuple(vals), out.row_nnz, overflow)


def _replan_local_panels(plan: SpgemmPlan, ad, bps, out: PanelSpgemmOut,
                         cache: PlanCache) -> PanelSpgemmOut:
    """Single-device panel retry: the re-planning unit is (bucket × panel) —
    an overflow in one panel of one bucket re-executes ONLY that block (the
    other panels' outputs are reused verbatim), spliced by whole-block
    replacement since panel blocks are independent."""
    policy = _policy_of(plan)
    buckets = plan.binning.buckets
    npan = plan.n_panels
    caps = np.asarray(plan.panel_caps, dtype=np.int64).copy()
    nnzs = [[np.asarray(out.row_nnz[i][p], dtype=np.int64)
             for p in range(npan)] for i in range(len(buckets))]
    cols = [list(bc) for bc in out.cols]
    vals = [list(bv) for bv in out.vals]
    args = plan.device_args()
    tables = args[1 + len(buckets):] if plan.pop_quant else args[1:]
    plan.retries = 0
    plan.retry_events = []
    plan.degradations = []
    changed = False

    def rerun(i, p, new_cap, unit):
        bk = buckets[i]
        meta = _panel_meta(bk, plan.panel_deg_b[i], new_cap)
        pop = int(tables[i].shape[0])
        run = cache.executor(
            ("bucket-retry-panel", plan.shape_a, plan.shape_b,
             plan.cap_a, plan._panel_caps_dev[p], plan.use_kernel, meta,
             pop),
            lambda m=meta: _build_bucket_executor(m, plan.use_kernel,
                                                  cache))
        c2, v2, _, _ = _invoke_executor(
            run, dict(unit=unit, bucket=i, panel=p), ad, bps[p], tables[i])
        cols[i][p] = c2
        vals[i][p] = v2

    for attempt in range(1, policy.rounds + 1):
        bumps = []
        for i, bk in enumerate(buckets):
            if not bk.n_rows:
                continue
            for p in range(npan):
                need = int(nnzs[i][p][:bk.n_rows].max(initial=0))
                if need <= caps[i, p]:
                    continue
                new_cap = policy.clamp(
                    int(caps[i, p]),
                    _bumped_capacity(int(caps[i, p]), need, policy.growth,
                                     attempt))
                if new_cap > caps[i, p]:
                    bumps.append((i, p, need, new_cap))
        if not bumps:
            break
        plan.retries = attempt
        changed = True
        for i, p, need, new_cap in bumps:
            rerun(i, p, new_cap, "bucket-retry")
            plan.retry_events.append(dict(
                round=attempt, bucket=i, panel=p, old_cap=int(caps[i, p]),
                new_cap=new_cap, need=need))
            caps[i, p] = new_cap
    # exact-symbolic escalation per offending (bucket × panel) unit (§9)
    over = [(i, p) for i, bk in enumerate(buckets) if bk.n_rows
            for p in range(npan)
            if int(nnzs[i][p][:bk.n_rows].max(initial=0)) > caps[i, p]]
    if over and policy.exact_fallback:
        changed = True
        for i, p in over:
            bk = buckets[i]
            counts = predictor_mod.exact_row_counts(
                ad, bps[p], bk.rows, max_deg_a=bk.deg_a,
                max_deg_b=plan.panel_deg_b[i], route=bk.route, span=bk.span)
            need = int(counts.max(initial=1))
            new_cap = _exact_capacity(need, int(caps[i, p]) + 1)
            rerun(i, p, new_cap, "exact-fallback")
            plan.degradations.append(dict(
                kind="exact_symbolic", bucket=i, panel=p,
                old_cap=int(caps[i, p]), new_cap=int(new_cap),
                need=int(need)))
            caps[i, p] = new_cap
    if not changed:
        if over and policy.on_exhausted == "raise":
            raise CapacityExhaustedError(
                f"retry escalation exhausted with {int(out.overflow)} "
                f"entries still dropped (bucket×panel units {over})",
                buckets=[i for i, _ in over], observed=int(out.overflow),
                plan_key=_plan_key_id(plan))
        return out                     # fast path: nothing overflowed
    plan.panel_caps = caps
    overflow = 0
    for i, bk in enumerate(buckets):
        for p in range(npan):
            overflow += int(np.maximum(
                nnzs[i][p][:bk.n_rows] - caps[i, p], 0).sum())
    if overflow and policy.on_exhausted == "raise":
        bad = [(i, p) for i, bk in enumerate(buckets) if bk.n_rows
               for p in range(npan)
               if int(nnzs[i][p][:bk.n_rows].max(initial=0)) > caps[i, p]]
        raise CapacityExhaustedError(
            f"retry escalation exhausted with {overflow} entries still "
            f"dropped (bucket×panel units {bad})",
            buckets=[i for i, _ in bad], observed=int(overflow),
            plan_key=_plan_key_id(plan))
    return PanelSpgemmOut(tuple(tuple(bc) for bc in cols),
                          tuple(tuple(bv) for bv in vals),
                          out.row_nnz, jnp.int32(overflow))


def _replan_dist_panels(plan: SpgemmPlan, ad, g_val_host: np.ndarray,
                        out: DistSpgemmOut, cache: PlanCache
                        ) -> DistSpgemmOut:
    """Distributed panel retry: overflow is detected per (bucket × panel)
    across that panel's device column, and ONLY the offending (bucket ×
    panel) re-executes — one cached local per-bucket executor run per row
    shard, against the SAME gathered operands the SPMD pass used (no
    re-gather, no full-bucket SPMD re-run)."""
    policy = _policy_of(plan)
    pg = plan._panel_gather
    npan = plan.n_panels
    ncols_b = plan.shape_b[1]
    buckets = plan.binning.buckets
    tables = list(plan.shard_tables)
    caps = np.asarray(plan.panel_caps, dtype=np.int64).copy()
    # truncation threshold per (bucket, panel): the width the executor
    # ACTUALLY allocated — every panel of bucket i ran at t.capacity (the
    # max over panels after an earlier bump), which may exceed caps[i, p];
    # comparing against caps would re-execute blocks nothing truncated
    alloc = np.array([[int(t.capacity)] * npan for t in tables],
                     dtype=np.int64)
    nnzs = [np.asarray(x, dtype=np.int64) for x in out.row_nnz]  # (D, pb)
    cols = vals = None                 # materialized on first retry only
    plan.retries = 0
    plan.retry_events = []
    plan.degradations = []

    def shard_operands(s, d):
        ad_d = CSRDevice(rpt=ad.rpt, col=jnp.asarray(pg.a_col[s]),
                         val=ad.val, shape=plan.shape_a)
        bd_d = CSRDevice(rpt=jnp.asarray(pg.g_rpt[d]),
                         col=jnp.asarray(pg.g_col[d]),
                         val=jnp.asarray(g_val_host[d]),
                         shape=(pg.nref, ncols_b))
        return ad_d, bd_d

    def rerun(i, p, new_cap, unit):
        nonlocal cols, vals
        t = tables[i]
        meta = _panel_meta(buckets[i], plan.panel_deg_b[i], new_cap)
        run = cache.executor(
            ("bucket-retry-panel-dist", plan.shape_a, plan.shape_b,
             plan.cap_a, pg.nref, pg.ecap, plan.use_kernel, meta,
             t.rows_pb),
            lambda m=meta: _build_bucket_executor(m, plan.use_kernel,
                                                  cache))
        if new_cap > cols[i].shape[2]:
            grow = new_cap - cols[i].shape[2]
            cols[i] = np.concatenate(
                [cols[i], np.full(cols[i].shape[:2] + (grow,),
                                  COL_SENTINEL, np.int32)], axis=2)
            vals[i] = np.concatenate(
                [vals[i], np.zeros(vals[i].shape[:2] + (grow,),
                                   np.float32)], axis=2)
        for s in range(plan.row_shards):
            d = s * npan + p
            ad_d, bd_d = shard_operands(s, d)
            c2, v2, _, _ = _invoke_executor(
                run, dict(unit=unit, bucket=i, panel=p, shard=s),
                ad_d, bd_d, jnp.asarray(t.table[d]))
            cols[i][d, :, :new_cap] = np.asarray(c2)
            vals[i][d, :, :new_cap] = np.asarray(v2)

    for attempt in range(1, policy.rounds + 1):
        bumps = []
        for i, t in enumerate(tables):
            for p in range(npan):
                need = int(np.where(t.valid[p::npan], nnzs[i][p::npan],
                                    0).max(initial=0))
                if need <= alloc[i, p]:
                    continue
                new_cap = policy.clamp(
                    int(alloc[i, p]),
                    _bumped_capacity(int(caps[i, p]), need, policy.growth,
                                     attempt))
                if new_cap > alloc[i, p]:
                    bumps.append((i, p, need, new_cap))
        if not bumps:
            break
        if cols is None:
            cols = [np.asarray(c).copy() for c in out.cols]
            vals = [np.asarray(v).copy() for v in out.vals]
        plan.retries = attempt
        for i, p, need, new_cap in bumps:
            rerun(i, p, new_cap, "bucket-retry")
            plan.retry_events.append(dict(
                round=attempt, bucket=i, panel=p, old_cap=int(caps[i, p]),
                new_cap=new_cap, need=need))
            caps[i, p] = new_cap
            alloc[i, p] = new_cap
    # exact-symbolic escalation per offending (bucket × panel) unit, one
    # cached local executor run per row shard against the SAME gathered
    # operands the SPMD pass used (§9)
    over = []
    for i, t in enumerate(tables):
        for p in range(npan):
            need = int(np.where(t.valid[p::npan], nnzs[i][p::npan],
                                0).max(initial=0))
            if need > alloc[i, p]:
                over.append((i, p))
    if over and policy.exact_fallback:
        if cols is None:
            cols = [np.asarray(c).copy() for c in out.cols]
            vals = [np.asarray(v).copy() for v in out.vals]
        for i, p in over:
            bk = buckets[i]
            t = tables[i]
            need = 1
            for s in range(plan.row_shards):
                d = s * npan + p
                rows = t.table[d][t.valid[d]]
                if not rows.size:
                    continue
                ad_d, bd_d = shard_operands(s, d)
                counts = predictor_mod.exact_row_counts(
                    ad_d, bd_d, rows, max_deg_a=bk.deg_a,
                    max_deg_b=plan.panel_deg_b[i], route=bk.route,
                    span=bk.span)
                need = max(need, int(counts.max(initial=1)))
            new_cap = _exact_capacity(need, int(alloc[i, p]) + 1)
            rerun(i, p, new_cap, "exact-fallback")
            plan.degradations.append(dict(
                kind="exact_symbolic", bucket=i, panel=p,
                old_cap=int(caps[i, p]), new_cap=int(new_cap),
                need=int(need)))
            caps[i, p] = new_cap
            alloc[i, p] = new_cap
    if cols is None:
        if over and policy.on_exhausted == "raise":
            total = int(np.asarray(out.shard_overflow).sum())
            raise ShardFailureError(
                f"retry escalation exhausted with {total} entries still "
                f"dropped (bucket×panel units {over})",
                shards=[int(d) // npan for d in
                        np.flatnonzero(np.asarray(out.shard_overflow))],
                observed=total, plan_key=_plan_key_id(plan))
        return out                     # fast path: nothing overflowed
    plan.panel_caps = caps
    plan.shard_tables = tuple(
        dataclasses.replace(t, capacity=int(caps[i].max()))
        for i, t in enumerate(tables))
    dev_panel = np.arange(plan.num_shards) % npan
    overflow = np.zeros(plan.num_shards, dtype=np.int64)
    for i, t in enumerate(plan.shard_tables):
        # residual TRUNCATION (vs the allocated widths) — entries a block
        # narrower than its true nnz actually dropped, not bookkeeping caps
        cap_d = alloc[i, dev_panel][:, None]
        overflow += np.where(t.valid,
                             np.maximum(nnzs[i] - cap_d, 0), 0).sum(axis=1)
    if overflow.sum() and policy.on_exhausted == "raise":
        devs = np.flatnonzero(overflow)
        raise ShardFailureError(
            f"retry escalation exhausted with {int(overflow.sum())} entries "
            "still dropped",
            shards=[int(d) // npan for d in devs],
            observed=int(overflow.sum()), plan_key=_plan_key_id(plan))
    return DistSpgemmOut(tuple(cols), tuple(vals), out.row_nnz, overflow)


def _local_executor(plan: SpgemmPlan, cache: PlanCache):
    """The cached single-device executor of a replicated-B local plan."""
    metas = tuple(_bucket_meta(bk, cap)
                  for bk, cap in zip(plan.binning.buckets,
                                     plan.alloc.bucket_capacities))
    return cache.executor(
        _executor_key(plan, None),
        lambda: _build_local_executor(metas, plan.alloc.row_capacity,
                                      plan.use_kernel, cache,
                                      masked=plan.pop_quant))


def lower_local(plan: SpgemmPlan, a, b, *,
                cache: PlanCache | None = None):
    """Lower, without running, the executor :func:`execute` dispatches for
    a single-device replicated-B plan — ``.compile().memory_analysis()``
    then gives its device bytes.  After an :func:`execute` of the same plan
    the lowering and compile are served from JAX's in-memory caches."""
    if plan.distributed or plan.n_panels or not plan.binning.buckets:
        raise PlanMismatchError(
            "lower_local needs a single-device replicated-B plan with at "
            "least one bucket", plan_key=_plan_key_id(plan))
    cache = cache if cache is not None else _DEFAULT_CACHE
    ad, bd = _coerce_pair(plan, a, b)
    return _local_executor(plan, cache).lower(ad, bd, *plan.device_args())


def execute(plan: SpgemmPlan, a, b, *, mesh=None, cache: PlanCache | None = None):
    """Run the planned numeric phase.

    Single-device plans return a :class:`repro.core.spgemm.SpGEMMOut`;
    distributed plans return a :class:`DistSpgemmOut` (feed to
    :func:`reassemble`).  ``a``/``b`` may be host ``CSR`` (converted at the
    plan's padded capacities) or pre-converted ``CSRDevice``.  Executors are
    served from ``cache`` (default: the session cache) keyed on the plan's
    static signature — a second same-keyed plan reuses the compiled
    executable with zero retraces.

    Plans armed with ``retry_safety`` run the overflow re-planning loop: any
    bucket whose true ``row_nnz`` exceeded its capacity is re-executed at a
    bumped (pow2-rounded) capacity and spliced back — the plan's capacities
    are updated in place, so a subsequent :func:`execute` of the same plan
    allocates right the first time.
    """
    cache = cache if cache is not None else _DEFAULT_CACHE
    plan.recoveries = []               # observability covers the LAST execute
    if plan.n_panels:
        # the fingerprint check is an O(nnz) host pass — the PLANNED
        # operands (the common serving identity) skip it for free
        planned = plan._planned_pair[0] if plan._planned_pair is not None \
            else (None, None)
        if b is not planned[1]:
            b = _check_panel_operand(plan, b, "b")
        if plan.distributed and a is not planned[0]:
            # the gather baked A's remapped columns too — an A with a
            # different structure would pair its values with the plan's
            # index maps and compute a different matrix without complaint
            a = _check_panel_operand(plan, a, "a")
        ad = _coerce_one(plan, a, "a", 0)
        bd = None                      # B never replicates in panel mode
    else:
        ad, bd = _coerce_pair(plan, a, b)
    if not plan.binning.buckets:
        if plan.distributed:
            return DistSpgemmOut((), (), (),
                                 np.zeros(plan.num_shards, dtype=np.int64))
        if plan.n_panels:
            return PanelSpgemmOut((), (), (), jnp.int32(0))
        cap = plan.alloc.row_capacity
        return SpGEMMOut(jnp.full((0, cap), COL_SENTINEL, jnp.int32),
                         jnp.zeros((0, cap), jnp.float32),
                         jnp.zeros((0,), jnp.int32), jnp.int32(0))

    wave_kw = ({} if plan.dispatch_budget is None else
               dict(budget=plan.dispatch_budget,
                    priced_s=_plan_priced_seconds(plan), cache=cache))
    if not plan.distributed:
        if plan.n_panels:
            metas = tuple(
                tuple(_panel_meta(bk, plan.panel_deg_b[i],
                                  int(plan.panel_caps[i, p]))
                      for p in range(plan.n_panels))
                for i, bk in enumerate(plan.binning.buckets))
            run = cache.executor(
                _executor_key(plan, None),
                lambda: _build_local_panel_executor(
                    metas, plan.use_kernel, cache, masked=plan.pop_quant))
            bps = _panel_operands_local(plan, b)
            try:
                out = _invoke_executor(run, dict(unit="local-panels"),
                                       ad, bps, *plan.device_args()[1:],
                                       **wave_kw)
            except StragglerError as e:
                # a straggling fused wave replays per (bucket × panel) unit
                # — completed units checkpoint in the recovery ledger
                from . import recovery as recovery_mod
                out = recovery_mod.recover_local_panels(plan, ad, bps,
                                                        cache, e)
            if plan.retry_policy is not None or plan.retry_safety > 0:
                out = _replan_local_panels(plan, ad, bps, out, cache)
            return out
        run = _local_executor(plan, cache)
        try:
            out = _invoke_executor(run, dict(unit="local"),
                                   ad, bd, *plan.device_args(), **wave_kw)
        except StragglerError as e:
            from . import recovery as recovery_mod
            out = recovery_mod.recover_local(plan, ad, bd, cache, e)
        if plan.retry_policy is not None or plan.retry_safety > 0:
            out = _replan_local(plan, ad, bd, out, cache)
        return out

    mesh = mesh if mesh is not None else plan.mesh
    if mesh is None:
        raise PlanMismatchError(
            "distributed plan needs a mesh (plan_spgemm(mesh=...)"
            " or execute(..., mesh=...))", plan_key=_plan_key_id(plan))
    if int(mesh.shape[plan.axis]) != plan.num_shards:
        raise PlanMismatchError(
            f"plan was built for {plan.num_shards} shards but mesh axis "
            f"{plan.axis!r} has {int(mesh.shape[plan.axis])} devices — "
            "re-plan with this mesh",
            observed=int(mesh.shape[plan.axis]), planned=plan.num_shards,
            plan_key=_plan_key_id(plan))
    if plan.n_panels:
        pg = plan._panel_gather
        metas = tuple(_panel_meta(bk, db, t.capacity)
                      for bk, db, t in zip(plan.binning.buckets,
                                           plan.panel_deg_b,
                                           plan.shard_tables))
        run = cache.executor(
            _executor_key(plan, mesh),
            lambda: _build_panel_dist_executor(
                metas, plan.shape_a, pg.nref, plan.shape_b[1], mesh,
                plan.axis, plan.use_kernel, cache))
        g_val_host = _gather_panel_values(pg, b)
        a_col_d, g_rpt_d, g_col_d = _panel_dist_args(plan)
        try:
            flat = _invoke_executor(run, dict(unit="dist-panels"),
                                    ad.rpt, ad.val, a_col_d, g_rpt_d,
                                    g_col_d, jnp.asarray(g_val_host),
                                    *plan.device_args(), **wave_kw)
        except ShardFailureError as e:
            # a failed fused wave re-executes per (bucket × device) unit
            # against the SAME host-side gathered operands; a persistently
            # dead device's units migrate whole to survivors (DESIGN.md §12)
            from . import recovery as recovery_mod
            return recovery_mod.recover_dist_panels(plan, ad, g_val_host,
                                                    cache, e)
        cols, vals, nnzs = flat[0::3], flat[1::3], flat[2::3]
        overflow = np.zeros(plan.num_shards, dtype=np.int64)
        for t, n in zip(plan.shard_tables, nnzs):
            over = np.maximum(np.asarray(n, dtype=np.int64) - t.capacity, 0)
            overflow += np.where(t.valid, over, 0).sum(axis=1)
        out = DistSpgemmOut(tuple(cols), tuple(vals), tuple(nnzs), overflow)
        if plan.retry_policy is not None or plan.retry_safety > 0:
            out = _replan_dist_panels(plan, ad, g_val_host, out, cache)
        return out
    metas = tuple(_bucket_meta(bk, t.capacity)
                  for bk, t in zip(plan.binning.buckets, plan.shard_tables))
    run = cache.executor(
        _executor_key(plan, mesh),
        lambda: _build_dist_executor(metas, mesh, plan.axis,
                                     plan.use_kernel, cache))
    try:
        flat = _invoke_executor(run, dict(unit="dist"),
                                ad, bd, *plan.device_args(), **wave_kw)
    except ShardFailureError as e:
        # the fused SPMD wave is all-or-nothing; recovery re-executes it as
        # per-(bucket × shard) units, checkpointing each as it lands, and
        # re-homes a lost shard's rows across survivors (DESIGN.md §12)
        from . import recovery as recovery_mod
        return recovery_mod.recover_dist(plan, ad, bd, cache, e)
    cols, vals, nnzs = flat[0::3], flat[1::3], flat[2::3]
    overflow = np.zeros(plan.num_shards, dtype=np.int64)
    for t, n in zip(plan.shard_tables, nnzs):
        over = np.maximum(np.asarray(n, dtype=np.int64) - t.capacity, 0)
        overflow += np.where(t.valid, over, 0).sum(axis=1)
    out = DistSpgemmOut(tuple(cols), tuple(vals), tuple(nnzs), overflow)
    if plan.retry_policy is not None or plan.retry_safety > 0:
        out = _replan_dist(plan, ad, bd, out, cache, mesh)
    return out


# --------------------------------------------------------------------------- #
# Reassembly (host-side; tests/examples)
# --------------------------------------------------------------------------- #
def _check_overflow(total: int, per_shard, on_overflow: str) -> None:
    if on_overflow not in ("raise", "ignore"):
        raise PlanMismatchError(f"on_overflow must be 'raise' or 'ignore', "
                                f"got {on_overflow!r}")
    if total and on_overflow == "raise":
        shards = [int(s) for s in np.asarray(per_shard)]
        raise CapacityExhaustedError(
            f"SpGEMM overflow: {total} entries dropped "
            f"(per shard: {shards}); re-plan with a higher safety factor "
            "or pass on_overflow='ignore'",
            observed=int(total), shards=shards)


def reassemble(plan: SpgemmPlan, out, ncols: int | None = None, *,
               on_overflow: str = "raise") -> CSR:
    """Stitch an :func:`execute` result back into one host CSR.

    Accepts a local ``SpGEMMOut`` or a distributed ``DistSpgemmOut``.
    Overflow (entries dropped for capacity) RAISES by default instead of
    silently truncating the result — pass ``on_overflow="ignore"`` to get
    the truncated matrix anyway.
    """
    ncols = int(ncols if ncols is not None else plan.shape_b[1])
    nrows = plan.shape_a[0]
    rows_out = [np.zeros(0, np.int64)]
    cols_out = [np.zeros(0, np.int64)]
    vals_out = [np.zeros(0, np.float32)]
    if isinstance(out, PanelSpgemmOut):
        # panels partition the column space: collecting every (bucket, panel)
        # block as COO and letting from_coo's stable sort order the entries
        # restores the single-matrix layout bitwise (DESIGN.md §8)
        _check_overflow(int(out.overflow), [int(out.overflow)], on_overflow)
        for i, bk in enumerate(plan.binning.buckets):
            if bk.n_rows == 0:
                continue
            for p in range(plan.n_panels):
                c_b = np.asarray(out.cols[i][p])[:bk.n_rows]
                v_b = np.asarray(out.vals[i][p])[:bk.n_rows]
                m = c_b != COL_SENTINEL
                counts = m.sum(axis=1)
                rows_out.append(np.repeat(bk.rows.astype(np.int64), counts))
                cols_out.append(c_b[m].astype(np.int64))
                vals_out.append(v_b[m])
        return CSR.from_coo(np.concatenate(rows_out),
                            np.concatenate(cols_out),
                            np.concatenate(vals_out).astype(np.float32),
                            (nrows, ncols), dedup=False, validate=False)
    if isinstance(out, DistSpgemmOut):
        _check_overflow(int(out.shard_overflow.sum()), out.shard_overflow,
                        on_overflow)
        for t, c_b, v_b in zip(plan.shard_tables, out.cols, out.vals):
            cap = t.capacity
            c_b = np.asarray(c_b).reshape(-1, cap)     # (S·rows_pb, cap)
            v_b = np.asarray(v_b).reshape(-1, cap)
            m = (c_b != COL_SENTINEL) & t.valid.reshape(-1)[:, None]
            counts = m.sum(axis=1)
            rows_out.append(np.repeat(
                t.table.reshape(-1).astype(np.int64), counts))
            cols_out.append(c_b[m].astype(np.int64))
            vals_out.append(v_b[m])
    else:
        _check_overflow(int(out.overflow), [int(out.overflow)], on_overflow)
        col = np.asarray(out.col)
        val = np.asarray(out.val)
        m = col != COL_SENTINEL
        counts = m.sum(axis=1)
        rows_out.append(np.repeat(np.arange(nrows, dtype=np.int64), counts))
        cols_out.append(col[m].astype(np.int64))
        vals_out.append(val[m])
    return CSR.from_coo(np.concatenate(rows_out), np.concatenate(cols_out),
                        np.concatenate(vals_out).astype(np.float32),
                        (nrows, ncols), dedup=False, validate=False)
