"""Hybrid accumulator backend: bitmask/dense-SPA vs sort/ESC routes.

The equivalence contract (DESIGN.md §5): symbolic ``z*``/``f*`` are
bitwise-equal across routes (distinct counts are order-invariant); numeric
``col``/``row_nnz``/``overflow`` are identical with ``val`` to float
tolerance (accumulation order differs).  Routing is a plan-time decision:
auto plans must never put a bucket on SPA when its dense column tile would
bust the VMEM lane budget.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal CI image — deterministic tests must still run
    from hypothesis_shim import given, settings, st

from repro.sparse import random as sprand
from repro.sparse.formats import CSR, spgemm_dense_oracle
from repro.core import binning, csr, oracle, predictor, spgemm
from repro.core.flop import flop_per_row
from repro.kernels import ops, ref


def _families():
    """One small matrix pair per suite family (er/pl/rmat/band/fem)."""
    return [
        ("er", sprand.erdos_renyi(400, 400, 4, seed=31),
         sprand.erdos_renyi(400, 400, 3, seed=32)),
        ("pl", sprand.power_law(500, 500, 5, 1.5, seed=33),
         sprand.power_law(500, 500, 4, 1.6, seed=34)),
        ("rmat", sprand.rmat(400, 400, 2400, seed=35),
         sprand.rmat(400, 400, 2000, seed=36)),
        ("band", sprand.banded(500, 500, 10, 14, seed=37),
         sprand.banded(500, 500, 8, 12, seed=38)),
        ("fem", sprand.banded(300, 300, 24, 16, seed=39),
         sprand.banded(300, 300, 20, 14, seed=40)),
    ]


_IDS = [f[0] for f in _families()]


# --------------------------------------------------------------------------- #
# symbolic: dense/bitmask distinct == sorted distinct (bitwise)
# --------------------------------------------------------------------------- #
def test_count_distinct_dense_equals_sorted():
    for _, a, b in _families():
        ad, bd = csr.to_device(a), csr.to_device(b)
        mda, mdb = int(a.row_nnz.max()), int(b.row_nnz.max())
        rows = predictor.draw_sample_rows(jax.random.PRNGKey(0), a.nrows, 50)
        cols, _ = predictor.gather_sampled_products(ad, bd, rows, mda, mdb)
        np.testing.assert_array_equal(
            np.asarray(predictor.count_distinct_sorted(cols)),
            np.asarray(predictor.count_distinct_dense(cols, b.ncols)))


@pytest.mark.parametrize("samples,block", [(8, 8), (37, 8), (5, 16)])
def test_bitmask_kernel_sweep(samples, block):
    a = sprand.banded(200, 200, 8, 12, seed=3)
    b = sprand.erdos_renyi(200, 160, 5, seed=4)
    ad, bd = csr.to_device(a), csr.to_device(b)
    mda, mdb = int(a.row_nnz.max()), int(b.row_nnz.max())
    rows = predictor.draw_sample_rows(jax.random.PRNGKey(samples), 200, samples)
    zk, fk = ops.bitmask_symbolic(ad, bd, rows, mda, mdb, block_samples=block)
    zr, fr = ref.bitmask_symbolic_ref(ad, bd, rows, mda, mdb)
    zs, fs = ref.sampled_symbolic_ref(ad, bd, rows, mda, mdb)
    assert int(zk) == int(zr) == int(zs)
    assert int(fk) == int(fr) == int(fs)


def test_fused_bitmask_matches_fused_sort():
    _, a, b = _families()[3]
    ad, bd = csr.to_device(a), csr.to_device(b)
    mda, mdb = int(a.row_nnz.max()), int(b.row_nnz.max())
    rows = predictor.draw_sample_rows(jax.random.PRNGKey(7), a.nrows, 21)
    ze, fe, fle = ops.fused_flop_symbolic(ad, bd, rows, mda, mdb)
    zs, fs, fls = ops.fused_flop_symbolic_routed(
        ad, bd, rows, max_deg_a=mda, max_deg_b=mdb, route=binning.ROUTE_SPA)
    assert int(ze) == int(zs) and int(fe) == int(fs)
    np.testing.assert_array_equal(np.asarray(fle), np.asarray(fls))


# --------------------------------------------------------------------------- #
# numeric: dense-SPA kernel / jnp path == ESC (col/nnz/overflow exact)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("cap,tile", [(4, 64), (16, 64), (16, 256), (64, 128)])
def test_spa_numeric_kernel_sweep(cap, tile):
    """Includes tiled runs (tile < next_pow2(ncols)) and overflow caps."""
    a = sprand.banded(150, 150, 12, 6, seed=9)   # heavy collisions
    ad = csr.to_device(a)
    mda = int(a.row_nnz.max())
    rows = jnp.arange(150, dtype=jnp.int32)
    ck, vk, nk, ofk = ops.spgemm_numeric_spa(
        ad, ad, rows, max_deg_a=mda, max_deg_b=mda, row_capacity=cap,
        tile_n=tile, block_rows=8)
    cr_, vr_, nr_, ofr = ref.spgemm_numeric_ref(ad, ad, rows, mda, mda, cap)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr_))
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr_), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr_))
    assert int(ofk) == int(ofr)


def test_spa_jnp_path_matches_esc():
    for _, a, b in _families()[:3]:
        ad, bd = csr.to_device(a), csr.to_device(b)
        mda, mdb = int(a.row_nnz.max()), int(b.row_nnz.max())
        rows = jnp.asarray(np.arange(0, a.nrows, 3, dtype=np.int32))
        oe = spgemm.spgemm_rows(ad, bd, rows, row_capacity=16, max_deg_a=mda,
                                max_deg_b=mdb, block_rows=32)
        os_ = spgemm.spgemm_rows_spa(ad, bd, rows, row_capacity=16,
                                     max_deg_a=mda, max_deg_b=mdb,
                                     block_rows=32)
        np.testing.assert_array_equal(np.asarray(oe.col), np.asarray(os_.col))
        np.testing.assert_allclose(np.asarray(oe.val), np.asarray(os_.val),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(oe.row_nnz),
                                      np.asarray(os_.row_nnz))
        assert int(oe.overflow) == int(os_.overflow)


@pytest.mark.parametrize("tile", [128, 256])
def test_bin_jnp_path_matches_esc(tile):
    """Propagation-blocking jnp executor vs ESC: col/row_nnz/overflow
    bitwise, val to float tolerance — across bin widths (the bin window
    must cover the pow2-padded column space, so the count follows)."""
    for _, a, b in _families()[:3]:
        ad, bd = csr.to_device(a), csr.to_device(b)
        mda, mdb = int(a.row_nnz.max()), int(b.row_nnz.max())
        ntiles = binning.ceil_pow2(b.ncols) // tile
        rows = jnp.asarray(np.arange(0, a.nrows, 3, dtype=np.int32))
        oe = spgemm.spgemm_rows(ad, bd, rows, row_capacity=16, max_deg_a=mda,
                                max_deg_b=mdb, block_rows=32)
        ob = spgemm.spgemm_rows_bin(ad, bd, rows, row_capacity=16,
                                    max_deg_a=mda, max_deg_b=mdb,
                                    block_rows=32, tile_n=tile,
                                    n_tiles=ntiles)
        np.testing.assert_array_equal(np.asarray(oe.col), np.asarray(ob.col))
        np.testing.assert_allclose(np.asarray(oe.val), np.asarray(ob.val),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(oe.row_nnz),
                                      np.asarray(ob.row_nnz))
        assert int(oe.overflow) == int(ob.overflow)


@pytest.mark.parametrize("cap,tile,ntiles", [(4, 64, 4), (16, 64, 4),
                                             (16, 256, 1), (64, 128, 2)])
def test_bin_numeric_kernel_sweep(cap, tile, ntiles):
    """Bin Pallas kernel vs the ESC reference — tiled bin windows and
    overflow caps included."""
    a = sprand.banded(150, 150, 12, 6, seed=9)   # heavy collisions
    ad = csr.to_device(a)
    mda = int(a.row_nnz.max())
    rows = jnp.arange(150, dtype=jnp.int32)
    ck, vk, nk, ofk = ops.spgemm_numeric_bin(
        ad, ad, rows, max_deg_a=mda, max_deg_b=mda, row_capacity=cap,
        tile_n=tile, n_tiles=ntiles, block_rows=8)
    cr_, vr_, nr_, ofr = ref.spgemm_numeric_ref(ad, ad, rows, mda, mda, cap)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr_))
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr_), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nk), np.asarray(nr_))
    assert int(ofk) == int(ofr)


# --------------------------------------------------------------------------- #
# routing: forced esc/spa agree on every suite family (satellite contract)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name,a,b", _families(), ids=_IDS)
def test_forced_routes_agree_symbolic(name, a, b):
    ad, bd = csr.to_device(a), csr.to_device(b)
    rows = predictor.draw_sample_rows(jax.random.PRNGKey(1), a.nrows, 40)
    preds = {}
    for route in ("esc", "spa", "bin", "auto"):
        plan = binning.build_plan(a, b, route=route)
        preds[route] = predictor.proposed_predict_binned(ad, bd, rows, plan)
    for route in ("spa", "bin", "auto"):
        assert int(preds["esc"].sampled_nnz) == int(preds[route].sampled_nnz)
        assert int(preds["esc"].sampled_flop) == int(preds[route].sampled_flop)
        assert float(preds["esc"].nnz_total) == float(preds[route].nnz_total)
        np.testing.assert_array_equal(np.asarray(preds["esc"].structure),
                                      np.asarray(preds[route].structure))


@pytest.mark.parametrize("name,a,b", _families(), ids=_IDS)
def test_forced_routes_agree_numeric(name, a, b):
    ad, bd = csr.to_device(a), csr.to_device(b)
    floprc, _ = flop_per_row(ad, bd)
    rows = predictor.draw_sample_rows(jax.random.PRNGKey(2), a.nrows, 40)
    plan_e = binning.build_plan(a, b, route="esc")
    pred = predictor.proposed_predict_binned(ad, bd, rows, plan_e)
    alloc = predictor.AllocationPlan.from_prediction(
        np.asarray(pred.structure), np.asarray(floprc), safety=1.3)
    outs = {route: spgemm.spgemm_binned(
                ad, bd, binning.build_plan(a, b, route=route),
                alloc=alloc.row_capacity)
            for route in ("esc", "spa", "bin", "auto")}
    for route in ("spa", "bin", "auto"):
        np.testing.assert_array_equal(np.asarray(outs["esc"].col),
                                      np.asarray(outs[route].col))
        np.testing.assert_allclose(np.asarray(outs["esc"].val),
                                   np.asarray(outs[route].val),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(outs["esc"].row_nnz),
                                      np.asarray(outs[route].row_nnz))
        assert int(outs["esc"].overflow) == int(outs[route].overflow)


def _edge_pair(case):
    """(a, b, rows, row_capacity, block_rows) for one compaction edge case."""
    rng = np.random.default_rng(61)

    def mat(r, c, shape):
        r, c = np.asarray(r), np.asarray(c)
        return CSR.from_coo(r, c, rng.uniform(0.5, 1.5, r.size), shape)

    if case == "long_run":
        # row 0 reaches 40 B rows that all hold column 7: one 40-long run
        a = mat(np.r_[np.zeros(40, int), np.arange(1, 41)],
                np.r_[np.arange(40), np.arange(40)], (41, 40))
        b = mat(np.r_[np.arange(40), np.arange(40)],
                np.r_[np.full(40, 7), rng.integers(0, 96, 40)], (40, 96))
        return a, b, np.arange(41), 8, 16
    if case == "empty_rows":
        a = sprand.erdos_renyi(96, 80, 3, seed=62)
        keep = np.repeat(np.arange(96), a.row_nnz) % 3 != 0
        a = mat(np.repeat(np.arange(96), a.row_nnz)[keep], a.col[keep],
                (96, 80))
        b = sprand.erdos_renyi(80, 120, 3, seed=63)
        b = mat(np.repeat(np.arange(80), b.row_nnz)[np.repeat(
            np.arange(80) % 4 != 0, b.row_nnz)], b.col[np.repeat(
                np.arange(80) % 4 != 0, b.row_nnz)], (80, 120))
        return a, b, np.arange(96), 16, 32
    a = sprand.erdos_renyi(120, 100, 4, seed=64)
    b = sprand.erdos_renyi(100, 150, 3, seed=65)
    if case == "overflow":
        return a, b, np.arange(0, 120, 2), 4, 16
    if case == "wide_capacity":      # more slots than gathered lanes
        w = int(a.row_nnz.max()) * int(b.row_nnz.max())
        return a, b, np.arange(120), 2 * w, 32
    # pad_rows: 37 rows in blocks of 16, the last (repeated) row overflowing
    rows = np.r_[np.arange(36), int(np.argmax(a.row_nnz))]
    return a, b, rows, 6, 16


_EDGE_CASES = ("overflow", "long_run", "empty_rows", "wide_capacity",
               "pad_rows")


@pytest.mark.parametrize("route", ("esc", "spa", "bin"))
@pytest.mark.parametrize("case", _EDGE_CASES)
def test_route_compaction_edge_cases_match_oracle(case, route):
    """Every route's sorted placement against the numpy oracle: ``col``,
    ``row_nnz`` and ``overflow`` exact (the first ``row_capacity`` distinct
    columns ascending, true counts, overflow over real rows only), ``val``
    to float tolerance."""
    a, b, rows, cap, block = _edge_pair(case)
    ad, bd = csr.to_device(a), csr.to_device(b)
    kw = dict(row_capacity=cap, max_deg_a=max(1, int(a.row_nnz.max())),
              max_deg_b=max(1, int(b.row_nnz.max())), block_rows=block)
    rows_d = jnp.asarray(rows, jnp.int32)
    if route == "esc":
        out = spgemm.spgemm_rows(ad, bd, rows_d, **kw)
    elif route == "spa":
        out = spgemm.spgemm_rows_spa(ad, bd, rows_d, **kw)
    else:
        out = spgemm.spgemm_rows_bin(
            ad, bd, rows_d, **kw, tile_n=32,
            n_tiles=binning.ceil_pow2(b.ncols) // 32)
    owner, col = oracle.expand_products(a, b, rows)
    dense = spgemm_dense_oracle(a, b)
    want_c = np.full((len(rows), cap), csr.COL_SENTINEL, np.int64)
    want_v = np.zeros((len(rows), cap), np.float32)
    want_n = np.zeros(len(rows), np.int64)
    for i, r in enumerate(rows):
        cs = np.unique(col[owner == i])
        want_n[i] = cs.size
        want_c[i, :min(cap, cs.size)] = cs[:cap]
        want_v[i, :min(cap, cs.size)] = dense[r, cs[:cap]]
    np.testing.assert_array_equal(np.asarray(out.col), want_c)
    np.testing.assert_array_equal(np.asarray(out.row_nnz), want_n)
    assert int(out.overflow) == int(np.maximum(want_n - cap, 0).sum())
    np.testing.assert_allclose(np.asarray(out.val), want_v, rtol=1e-5,
                               atol=1e-5)
    if case == "overflow":
        assert (want_n > cap).any()
    if case == "empty_rows":
        assert (want_n == 0).any()
    if case == "long_run":
        assert (col[owner == 0] == 7).sum() == 40
    if case == "pad_rows":
        assert len(rows) % block and want_n[-1] > cap


@pytest.mark.parametrize("fn,extra,scatters", [
    (spgemm.spgemm_rows, {}, 0),
    (spgemm.spgemm_rows_spa, {}, 2),
    (spgemm.spgemm_rows_bin, dict(tile_n=32, n_tiles=4), 2),
], ids=("esc", "spa", "bin"))
def test_lowered_executors_place_by_sort(fn, extra, scatters):
    """The compaction is a keyed sort, not a scatter: the ESC route lowers
    with no scatter at all, SPA and BIN with only their dense window's two
    (value add and presence set)."""
    a = sprand.power_law(64, 64, 4, 1.5, seed=1)
    ad = csr.to_device(a)
    mda = int(a.row_nnz.max())
    text = fn.lower(ad, ad, jnp.arange(64, dtype=jnp.int32), row_capacity=8,
                    max_deg_a=mda, max_deg_b=mda, block_rows=16,
                    **extra).as_text()
    assert text.count('"stablehlo.scatter"') == scatters
    assert text.count('"stablehlo.sort"') >= 1


def test_forced_routes_agree_kernel_path():
    """Kernel (Pallas) dispatch: routed numeric + symbolic agree too."""
    _, a, b = _families()[3]
    ad, bd = csr.to_device(a), csr.to_device(b)
    rows = predictor.draw_sample_rows(jax.random.PRNGKey(4), a.nrows, 24)
    plans = {r: binning.build_plan(a, b, route=r)
             for r in ("esc", "spa", "bin")}
    pe = predictor.proposed_predict_binned(ad, bd, rows, plans["esc"],
                                           use_kernel=True)
    oe = spgemm.spgemm_binned(ad, bd, plans["esc"], alloc=24, use_kernel=True)
    for r in ("spa", "bin"):
        pr = predictor.proposed_predict_binned(ad, bd, rows, plans[r],
                                               use_kernel=True)
        assert int(pe.sampled_nnz) == int(pr.sampled_nnz)
        or_ = spgemm.spgemm_binned(ad, bd, plans[r], alloc=24,
                                   use_kernel=True)
        np.testing.assert_array_equal(np.asarray(oe.col),
                                      np.asarray(or_.col))
        np.testing.assert_allclose(np.asarray(oe.val), np.asarray(or_.val),
                                   rtol=1e-5, atol=1e-5)
        assert int(oe.overflow) == int(or_.overflow)


# --------------------------------------------------------------------------- #
# routing: the VMEM-budget property + cost-model direction
# --------------------------------------------------------------------------- #
@given(st.integers(0, 10_000), st.integers(8, 4096), st.integers(10, 18))
@settings(max_examples=25, deadline=None)
def test_auto_plan_spa_fits_lane_budget(seed, ncols, budget_exp):
    """build_plan(route="auto") must never pick SPA when the dense column
    tile would exceed the VMEM lane budget: every SPA bucket satisfies
    block_rows·tile_n ≤ budget, covers the column space in ONE tile, and
    keeps ≥ spa_min_block_rows rows per block."""
    budget = 1 << budget_exp
    rng = np.random.default_rng(seed)
    a = sprand.erdos_renyi(64, ncols, int(rng.integers(1, 9)), seed=seed)
    b = sprand.erdos_renyi(ncols, ncols, int(rng.integers(1, 9)),
                           seed=seed + 1)
    plan = binning.build_plan(a, b, lane_budget=budget)
    for bk in plan.buckets:
        if bk.route == binning.ROUTE_SPA:
            assert bk.n_tiles == 1
            assert bk.tile_n >= binning.ceil_pow2(ncols) or \
                bk.tile_n * bk.n_tiles >= ncols
            assert bk.block_rows * bk.tile_n <= budget
            assert budget // bk.tile_n >= binning.DEFAULT_SPA_MIN_BLOCK_ROWS
        elif bk.route == binning.ROUTE_BIN:
            # auto only picks BIN when the layout yields ≥ 2 bins, the bins
            # cover the bucket's pow2 extent bound, and the block holds ALL
            # bins at once under the budget (up to the single-row floor)
            assert bk.n_tiles >= 2
            assert bk.tile_n * bk.n_tiles >= bk.span
            assert bk.block_rows * bk.tile_n * bk.n_tiles <= \
                max(budget, bk.tile_n * bk.n_tiles)
        else:
            assert bk.tile_n == 0 and bk.n_tiles == 0


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_forced_spa_tiles_cover_columns(seed):
    """Forced SPA always tiles instead of being rejected — tiles cover the
    pow2-padded column space and each tile block fits the budget."""
    rng = np.random.default_rng(seed)
    ncols = int(rng.integers(8, 3000))
    budget = 1 << int(rng.integers(8, 16))
    a = sprand.erdos_renyi(48, ncols, 3, seed=seed)
    b = sprand.erdos_renyi(ncols, ncols, 3, seed=seed + 1)
    plan = binning.build_plan(a, b, route="spa", lane_budget=budget)
    for bk in plan.buckets:
        assert bk.route == binning.ROUTE_SPA
        assert bk.tile_n * bk.n_tiles >= ncols
        assert bk.tile_n % binning.SPA_MIN_TILE == 0 or \
            bk.tile_n == binning.ceil_pow2(ncols)
        assert bk.block_rows * bk.tile_n <= max(budget, bk.tile_n)


def test_cost_model_routes_expected_regimes():
    """The regimes the router exists to separate (DESIGN.md §5): banded/FEM
    (wide buffers, compact columns) → SPA; low-degree ER and wide power-law
    column spaces → ESC."""
    # banded 2000-col: w≈150, sort pays ~64 stages/lane → SPA
    band = sprand.banded(2000, 2000, 12, 16, seed=13)
    assert binning.build_plan(band, band).route_rows()["esc"] == 0
    # power-law 3000-col: tile would leave <64 rows/block → all ESC
    pl = sprand.power_law(3000, 3000, 5, 1.5, seed=11)
    plb = sprand.power_law(3000, 3000, 4, 1.6, seed=12)
    assert binning.build_plan(pl, plb).route_rows()["spa"] == 0
    # tiny-width buckets: sorting a 4-lane buffer beats touching even a
    # narrow 128-lane tile — ESC; mid-width with narrow extent flips to SPA;
    # the same mid-width against a full-span extent stays ESC
    assert binning.choose_route(2, 2, 2000, 64)[0] == binning.ROUTE_ESC
    assert binning.choose_route(12, 12, 2000, 64)[0] == binning.ROUTE_SPA
    assert binning.choose_route(12, 12, 2000)[0] == binning.ROUTE_ESC
    # low-degree ER on a wide B keeps its narrow buckets on ESC
    er = sprand.erdos_renyi(2000, 2000, 3, seed=25)
    plan = binning.build_plan(er, er)
    narrow = [bk for bk in plan.buckets if bk.width <= 16]
    assert narrow and all(bk.route == binning.ROUTE_ESC for bk in narrow)
    # hub-width buckets over a wide extent (power-law hubs): one gather +
    # streamed fixed-width bins beats both the sort depth and SPA's
    # per-tile product re-gather → BIN
    rt, tile, ntiles = binning.choose_route(128, 64, 100_000, 4096)
    assert rt == binning.ROUTE_BIN
    assert tile == binning.BIN_TILE and tile * ntiles >= 4096
    # narrow extents collapse to a single bin — the ≥2-bin gate keeps
    # banded/FEM work off BIN entirely (it would just be a worse SPA)
    assert binning.route_costs(8, 8, 2000, 64)["bin_n"] == 1
    assert binning.choose_route(12, 12, 2000, 64)[0] != binning.ROUTE_BIN


def test_signature_includes_route():
    """Route and tile are compile-cache keys: forced esc/spa/bin plans of
    the same matrix must NOT share signatures (different programs)."""
    _, a, b = _families()[3]
    pe = binning.build_plan(a, b, route="esc")
    ps = binning.build_plan(a, b, route="spa")
    pb = binning.build_plan(a, b, route="bin")
    assert set(pe.signatures()).isdisjoint(ps.signatures())
    assert set(pe.signatures()).isdisjoint(pb.signatures())
    assert set(ps.signatures()).isdisjoint(pb.signatures())
    assert all(len(s) == 6 for s in pe.signatures())


# --------------------------------------------------------------------------- #
# bin route on the 4-device distributed path (subprocess, like test_panels)
# --------------------------------------------------------------------------- #
BIN_DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax

from repro.sparse import random as sprand
from repro.sparse.formats import spgemm_dense_oracle
from repro.core import plan as plan_mod, spgemm

mesh = jax.make_mesh((4,), ("data",))
fams = [
    ("pl", sprand.power_law(500, 500, 5, 1.5, seed=33),
     sprand.power_law(500, 500, 4, 1.6, seed=34)),
    ("band", sprand.banded(400, 400, 10, 14, seed=23),
     sprand.banded(400, 400, 8, 12, seed=24)),
]
out = {}
for fam, a, b in fams:
    p = plan_mod.plan_spgemm(a, b, mesh=mesh, safety=2.0, route="bin")
    res = plan_mod.execute(p, a, b)
    c = plan_mod.reassemble(p, res)
    pe = plan_mod.plan_spgemm(a, b, safety=2.0, route="esc",
                              sample_rows=p.sample_rows)
    oe = spgemm.spgemm_binned(pe.to_device(a, "a"), pe.to_device(b, "b"),
                              pe.binning, alloc=pe.alloc)
    ce = plan_mod.reassemble(pe, oe)
    out[fam] = dict(
        overflow=int(res.shard_overflow.sum()),
        routes=p.binning.route_rows(),
        rpt_eq=bool((c.rpt == ce.rpt).all()),
        col_eq=bool((c.col == ce.col).all()),
        vdiff=float(np.abs(c.val - ce.val).max()),
        ref_err=float(np.abs(c.to_dense()
                             - spgemm_dense_oracle(a, b)).max()),
    )
print(json.dumps(out))
"""


@pytest.mark.slow
def test_bin_route_distributed_4dev_matches_esc():
    """Forced-bin distributed numeric phase (4 host devices) is bitwise
    route-equivalent to the single-device ESC reference on the same sampled
    rows — the bin statics thread through the sharded executors unchanged."""
    import json as _json
    import os as _os
    import subprocess as _sp
    import sys as _sys
    src = _os.path.join(_os.path.dirname(__file__), "..", "src")
    env = dict(_os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    run = _sp.run([_sys.executable, "-c", BIN_DIST_SCRIPT], env=env,
                  capture_output=True, text=True, timeout=1800)
    assert run.returncode == 0, run.stderr[-3000:]
    rec = _json.loads(run.stdout.strip().splitlines()[-1])
    for fam in ("pl", "band"):
        r = rec[fam]
        assert r["routes"]["bin"] > 0 and r["routes"]["esc"] == 0, (fam, r)
        assert r["overflow"] == 0, (fam, r)
        assert r["rpt_eq"] and r["col_eq"], (fam, r)
        assert r["vdiff"] < 1e-4, (fam, r)
        assert r["ref_err"] < 1e-3, (fam, r)


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_forced_bin_layout_covers_span(seed):
    """Forced BIN always lays out bins instead of being rejected — the bins
    cover each bucket's pow2 extent bound with fixed-width tiles."""
    rng = np.random.default_rng(seed)
    ncols = int(rng.integers(8, 3000))
    a = sprand.erdos_renyi(48, ncols, 3, seed=seed)
    b = sprand.erdos_renyi(ncols, ncols, 3, seed=seed + 1)
    plan = binning.build_plan(a, b, route="bin")
    for bk in plan.buckets:
        assert bk.route == binning.ROUTE_BIN
        assert bk.n_tiles >= 1 and bk.tile_n * bk.n_tiles >= bk.span
        assert binning.SPA_MIN_TILE <= bk.tile_n <= \
            max(binning.BIN_TILE, binning.SPA_MIN_TILE)
