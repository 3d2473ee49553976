"""Unified plan/execute pipeline (core/plan.py): local parity, cache contract,
per-shard capacity sizing, and degenerate inputs.

The distributed executor itself runs under a 4-device mesh in
``tests/test_distributed.py`` (subprocess — device count must precede jax
init); everything here is single-device."""
import jax
import numpy as np
import pytest

from repro.sparse import random as sprand
from repro.sparse.formats import CSR, spgemm_dense_oracle
from repro.core import binning, csr, oracle, plan as plan_mod
from repro.core import predictor, spgemm


def _revalue(m: CSR, seed: int) -> CSR:
    """Same sparsity structure, fresh values — the serving scenario."""
    rng = np.random.default_rng(seed)
    return CSR(rpt=m.rpt.copy(), col=m.col.copy(),
               val=rng.standard_normal(m.nnz).astype(np.float32),
               shape=m.shape)


def _hub_matrix(m=400, hub_deg=200):
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(1, m), 2)
    cols = rng.integers(0, m, rows.size)
    hub_cols = rng.choice(m, hub_deg, replace=False)
    rows = np.concatenate([np.zeros(hub_deg, np.int64), rows])
    cols = np.concatenate([hub_cols, cols])
    vals = rng.standard_normal(rows.size).astype(np.float32)
    return CSR.from_coo(rows, cols, vals, (m, m))


# --------------------------------------------------------------------------- #
# local execute == spgemm_binned (bitwise)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name,a,b", [
    ("pl", sprand.power_law(500, 500, 5, 1.5, seed=21),
     sprand.power_law(500, 500, 4, 1.6, seed=22)),
    ("band", sprand.banded(400, 400, 10, 14, seed=23),
     sprand.banded(400, 400, 8, 12, seed=24)),
    ("er", sprand.erdos_renyi(400, 400, 4, seed=25),
     sprand.erdos_renyi(400, 400, 3, seed=26)),
], ids=["pl", "band", "er"])
def test_execute_local_matches_spgemm_binned(name, a, b):
    p = plan_mod.plan_spgemm(a, b, safety=2.0)
    out = plan_mod.execute(p, a, b)
    ob = spgemm.spgemm_binned(p.to_device(a, "a"), p.to_device(b, "b"),
                              p.binning, alloc=p.alloc)
    np.testing.assert_array_equal(np.asarray(out.col), np.asarray(ob.col))
    np.testing.assert_array_equal(np.asarray(out.val), np.asarray(ob.val))
    np.testing.assert_array_equal(np.asarray(out.row_nnz),
                                  np.asarray(ob.row_nnz))
    assert int(out.overflow) == int(ob.overflow)
    c = plan_mod.reassemble(p, out)
    np.testing.assert_allclose(c.to_dense(), spgemm_dense_oracle(a, b),
                               rtol=1e-4, atol=1e-4)


def test_execute_accepts_device_operands_and_checks_shapes():
    a = sprand.banded(200, 200, 6, 8, seed=3)
    p = plan_mod.plan_spgemm(a, a, safety=2.0)
    ad = p.to_device(a, "a")
    out = plan_mod.execute(p, ad, ad)
    assert int(out.overflow) == 0
    with pytest.raises(ValueError):
        p.to_device(sprand.banded(100, 100, 6, 8, seed=3), "a")


# --------------------------------------------------------------------------- #
# plan cache: signature-keyed executables, zero retraces in serving
# --------------------------------------------------------------------------- #
def test_plan_cache_zero_retraces_on_same_signature_pair():
    cache = plan_mod.PlanCache()
    a1 = sprand.banded(400, 400, 8, 12, seed=31)
    b1 = sprand.banded(400, 400, 6, 10, seed=32)
    p1 = plan_mod.plan_spgemm(a1, b1, safety=2.0)
    out1 = plan_mod.execute(p1, a1, b1, cache=cache)
    first = cache.stats()
    assert first["misses"] == 1 and first["traces"] >= 1

    # same structure, new values: same plan key → cached executable, and
    # the compile-count pin — ZERO additional traces
    a2, b2 = _revalue(a1, 41), _revalue(b1, 42)
    p2 = plan_mod.plan_spgemm(a2, b2, safety=2.0)
    assert p2.key == p1.key
    out2 = plan_mod.execute(p2, a2, b2, cache=cache)
    second = cache.stats()
    assert second["hits"] == 1
    assert second["traces"] == first["traces"], "serving pair retraced"
    # and the cached executable computes the right thing
    c2 = plan_mod.reassemble(p2, out2)
    np.testing.assert_allclose(c2.to_dense(), spgemm_dense_oracle(a2, b2),
                               rtol=1e-4, atol=1e-4)
    # row_nnz is structure-determined: bitwise across the pair
    np.testing.assert_array_equal(np.asarray(out1.row_nnz),
                                  np.asarray(out2.row_nnz))


def test_plan_key_differs_on_shape_and_safety():
    a = sprand.banded(300, 300, 8, 12, seed=33)
    p1 = plan_mod.plan_spgemm(a, a, safety=1.05)
    p2 = plan_mod.plan_spgemm(a, a, safety=3.0)
    b = sprand.banded(320, 320, 8, 12, seed=33)
    p3 = plan_mod.plan_spgemm(b, b, safety=1.05)
    assert p1.key != p3.key
    # different safety → different capacities → different executable key
    # (1.05 stays below the flopr ceiling, 3.0 saturates it)
    assert p1.alloc.bucket_capacities != p2.alloc.bucket_capacities
    assert p1.key != p2.key


def test_default_session_cache_is_used():
    a = sprand.erdos_renyi(150, 150, 3, seed=7)
    p = plan_mod.plan_spgemm(a, a, safety=2.0)
    before = plan_mod.plan_cache().stats()["misses"]
    plan_mod.execute(p, a, a)
    assert plan_mod.plan_cache().stats()["misses"] >= before


# --------------------------------------------------------------------------- #
# per-shard capacity sizing: the hub-row regression (satellite of ISSUE 3)
# --------------------------------------------------------------------------- #
def _legacy_global_pad_slots(a, num_shards=4, safety=1.3):
    """The retired global-pad sizing rule (``benchmarks/legacy_distributed``):
    every shard allocates rows_per_shard × ONE global row capacity sized by
    the worst predicted row in the whole matrix — inlined here so the
    regression pin survives the legacy path leaving the library."""
    flopr, _ = oracle.flop_per_row(a, a)
    pred = oracle.proposed_predict(a, a, seed=0)
    from repro.core import partition
    part = partition.balanced_contiguous(pred.structure, num_shards)
    rows_per_shard = int(max(np.diff(part.bounds).max(), 1))
    cap = int(min(np.ceil(pred.structure.max() * safety), flopr.max()))
    cap = max(8, -(-cap // 8) * 8)
    return rows_per_shard * cap


def test_hub_row_no_longer_inflates_other_shards_buffers():
    """The legacy global-pad path sized EVERY shard's buffers from the
    global max predicted row, so one hub row inflated all shards.  The
    unified plan isolates the hub in its own bucket: every other bucket's
    capacity is sized by its own rows, and the per-shard footprint drops by
    an order of magnitude."""
    a = _hub_matrix()
    legacy_slots = _legacy_global_pad_slots(a, num_shards=4)

    p = plan_mod.plan_spgemm(a, a, num_shards=4, safety=1.3)
    new_slots = p.shard_slots()
    assert new_slots * 5 < legacy_slots, (new_slots, legacy_slots)

    # the hub's capacity applies only to its own (tiny) bucket...
    hub_bucket = int(p.binning.row_bucket[0])
    caps = [t.capacity for t in p.shard_tables]
    assert caps[hub_bucket] == max(caps)
    assert p.binning.buckets[hub_bucket].n_rows < 50
    # ...and per-(bucket, shard) needs show shards WITHOUT the hub never
    # requiring the hub capacity for any other bucket
    hub_shard = int(np.searchsorted(p.partition.bounds, 0, side="right")) - 1
    other = np.delete(np.arange(4), hub_shard)
    non_hub = np.delete(np.arange(len(caps)), hub_bucket)
    if non_hub.size:
        assert p.shard_capacities[non_hub][:, other].max() < caps[hub_bucket]


def test_shard_tables_partition_rows_exactly():
    a = sprand.power_law(600, 600, 5, 1.5, seed=50)
    p = plan_mod.plan_spgemm(a, a, num_shards=4, safety=2.0)
    seen = []
    for t in p.shard_tables:
        for s in range(t.table.shape[0]):
            seen.append(t.table[s][t.valid[s]])
    seen = np.sort(np.concatenate(seen))
    np.testing.assert_array_equal(seen, np.arange(a.nrows))
    # every shard's valid rows fall inside its partition range
    for t in p.shard_tables:
        for s in range(4):
            ids = t.table[s][t.valid[s]]
            if ids.size:
                assert ids.min() >= p.partition.bounds[s]
                assert ids.max() < p.partition.bounds[s + 1]


# --------------------------------------------------------------------------- #
# degenerate inputs
# --------------------------------------------------------------------------- #
def test_empty_matrix_plans_and_reassembles():
    a = CSR(rpt=np.zeros(1, np.int64), col=np.zeros(0, np.int32),
            val=np.zeros(0, np.float32), shape=(0, 0))
    p = plan_mod.plan_spgemm(a, a)
    out = plan_mod.execute(p, a, a)
    c = plan_mod.reassemble(p, out)
    assert c.nnz == 0 and c.shape == (0, 0)


def test_all_zero_nnz_rows_reassemble_empty():
    """Every row empty → all shard outputs empty; reassemble must not crash
    (the legacy np.concatenate-of-empty-list bug, fixed alongside)."""
    a = CSR(rpt=np.zeros(6, np.int64), col=np.zeros(0, np.int32),
            val=np.zeros(0, np.float32), shape=(5, 5))
    p = plan_mod.plan_spgemm(a, a, min_rows=1)
    out = plan_mod.execute(p, a, a)
    c = plan_mod.reassemble(p, out)
    assert c.nnz == 0 and c.shape == (5, 5)


def test_reassemble_raises_on_overflow():
    a = sprand.banded(200, 200, 10, 12, seed=9)
    p = plan_mod.plan_spgemm(a, a, safety=2.0)
    # shrink every capacity to force dropped entries
    p.alloc = predictor.BinnedAllocationPlan(
        bucket_capacities=tuple(8 for _ in p.alloc.bucket_capacities),
        row_capacity=8, total_capacity=8 * a.nrows, safety=0.0)
    out = plan_mod.execute(p, a, a)
    assert int(out.overflow) > 0
    with pytest.raises(ValueError, match="overflow"):
        plan_mod.reassemble(p, out)
    with pytest.raises(ValueError, match="on_overflow"):
        plan_mod.reassemble(p, out, on_overflow="warn")   # typo-proof
    c = plan_mod.reassemble(p, out, on_overflow="ignore")
    assert c.nnz < int(np.asarray(out.row_nnz).sum())


def test_execute_rejects_mismatched_mesh():
    a = sprand.banded(200, 200, 6, 8, seed=13)
    p = plan_mod.plan_spgemm(a, a, num_shards=4, safety=2.0)
    mesh = jax.make_mesh((1,), ("data",))      # single-device test env
    with pytest.raises(ValueError, match="4 shards"):
        plan_mod.execute(p, a, a, mesh=mesh)


# --------------------------------------------------------------------------- #
# backends: use_kernel on a TPU is refused, typed, before any dispatch
# --------------------------------------------------------------------------- #
def test_use_kernel_refused_on_tpu_before_dispatch(monkeypatch):
    from repro.core.errors import PlanMismatchError, ShardFailureError
    from repro.kernels import ops
    from repro.serve.spgemm_service import ServiceConfig
    a = sprand.erdos_renyi(64, 64, 3, seed=5)
    cache = plan_mod.plan_cache()
    before = cache.stats()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(PlanMismatchError) as ei:
        plan_mod.plan_spgemm(a, a, use_kernel=True)
    assert not isinstance(ei.value, ShardFailureError)
    assert ei.value.context["kernels"] == list(ops.TPU_REFUSED_KERNELS)
    with pytest.raises(PlanMismatchError):
        ServiceConfig(use_kernel=True)
    assert cache.stats() == before          # nothing was built or run
    ServiceConfig()                         # the XLA path stays available


def test_lower_local_reuses_the_executed_executor():
    a = sprand.power_law(300, 300, 4, 1.6, seed=8)
    cache = plan_mod.PlanCache()
    plan = plan_mod.plan_spgemm(a, a, pop_quant=True)
    plan_mod.execute(plan, a, a, cache=cache)
    traces = cache.traces
    ma = plan_mod.lower_local(plan, a, a, cache=cache).compile() \
        .memory_analysis()
    assert ma.output_size_in_bytes > 0
    assert cache.traces == traces           # served from the plan cache
    dist = plan_mod.plan_spgemm(a, a, num_shards=2)
    with pytest.raises(plan_mod.PlanMismatchError):
        plan_mod.lower_local(dist, a, a)
