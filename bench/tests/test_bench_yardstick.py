"""The yardstick on its own: the trace reduction, the roofline byte count,
the reference, the comparison and the traffic generator."""
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

import check
import loads
import reference
import roofline
import tracing

DATA = Path(__file__).resolve().parent / "data"


def _csr(dense):
    dense = np.asarray(dense, dtype=np.float32)
    rows, cols = np.nonzero(dense)
    rpt = np.zeros(dense.shape[0] + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=dense.shape[0]), out=rpt[1:])
    return rpt, cols.astype(np.int32), dense[rows, cols]


# --------------------------------------------------------------- roofline
def test_byte_count_hand_counted():
    # A = [[1 2 0]       A·A: row 0 = 1·A0 + 2·A1 -> products 2 + 1 = 3
    #      [0 0 3]            row 1 = 3·A2        -> 1
    #      [4 0 0]]           row 2 = 4·A0        -> 2
    a = _csr([[1, 2, 0], [0, 0, 3], [4, 0, 0]])
    assert reference.flop(a, a) == 6
    rpt, col, val = reference.spgemm(a, a, 3)
    # C = [[1 2 6], [12 0 0], [4 8 0]]
    assert rpt.tolist() == [0, 3, 4, 6]
    assert col.tolist() == [0, 1, 2, 0, 0, 1]
    assert val.tolist() == [1, 2, 6, 12, 4, 8]
    # 8·nnz(A) + 8·FLOP + 8·NNZ(C) + 8·(m+1) = 8·4 + 8·6 + 8·6 + 8·4
    assert roofline.spgemm_bytes(3, 4, 6, 6) == 160


def test_peak_table_knows_v5e_and_refuses_others():
    assert roofline.peak("TPU v5 lite") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("cpu")


# --------------------------------------------------------- trace reduction
def test_summarize_hand_made_trace():
    ns = 1e9
    events = {
        "host": [["bench:window", 0, 10 * ns], ["bench:plan", 1 * ns, 3 * ns],
                 ["bench:execute", 3 * ns, 6 * ns],
                 ["bench:validate", 1 * ns, 2 * ns],
                 ["bench:reassemble", 6 * ns, 9 * ns]],
        "devices": {"/device:TPU:0": {
            "ops": [["sort.1", 3 * ns, 5 * ns], ["fusion.2", 4 * ns, 5.5 * ns],
                    ["fusion.2", 9.5 * ns, 11 * ns]],
            "modules": [["jit_run(7)", 3 * ns, 5.5 * ns],
                        ["jit_run(7)", 9.5 * ns, 11 * ns]]}},
    }
    s = tracing.summarize(events)
    assert s["window_s"] == 10
    assert s["busy_s"] == pytest.approx(2.5 + 0.5)     # clipped at 10
    assert s["device_ops"][0] == ["sort.1", 2.0]
    assert s["modules"] == {"jit_run": pytest.approx(3.0)}
    gaps = dict(s["idle_gaps"])
    # idle [0,3) and [5.5,9.5), split by the innermost span open:
    # [0,1) host, [1,2) validate, [2,3) plan, [5.5,6) execute,
    # [6,9) reassemble, [9,9.5) host
    assert gaps == pytest.approx({"host": 1.5, "validate": 1.0, "plan": 1.0,
                                  "execute": 0.5, "reassemble": 3.0})
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_summarize_finds_nothing_without_a_device():
    assert tracing.summarize({"host": [["bench:window", 0, 5]],
                              "devices": {}}) is None


def test_recorded_v5e_trace():
    """A small trace recorded on a TPU v5e (three sorts, each inside a
    ``bench:execute`` span, with sleeps between them)."""
    s = tracing.summarize(tracing.load(str(DATA / "v5e_probe.xplane.pb")))
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"][0][0] == "sort.6"
    assert set(s["modules"]) == {"jit__lambda"}
    gaps = dict(s["idle_gaps"])
    assert gaps["wait"] > 0.010              # 3 sleeps of 5 ms


def test_load_reads_bench_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1024.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("bench:execute"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = tracing.load(str(path))
    names = sorted(n for n, _, _ in events["host"])
    assert names == ["bench:execute", "bench:window"]
    assert events["devices"] == {}              # the CPU has no device plane


# ------------------------------------------------- reference and comparison
def _operand(law_params, law, rows, seed):
    cfg = {"law": law, "law_params": law_params,
           "values": {"dtype": "float32", "low": 0.5, "high": 1.5}}
    return loads.make_operand(cfg, rows, seed, 0, 0)


@pytest.mark.parametrize("law,params", [
    ("uniform", {"nnz_per_row": 4}),
    ("power_law", {"avg_nnz": 3.1, "alpha": 1.8})])
def test_reference_matches_dense_product(law, params):
    rows = 300
    a = _operand(params, law, rows, 11)
    dense = np.zeros((rows, rows))
    r = np.repeat(np.arange(rows), np.diff(a[0]))
    dense[r, a[1]] = a[2]
    want = dense @ dense
    rpt, col, val = reference.spgemm(a, a, rows, chunk=97)
    got = np.zeros_like(want)
    got[np.repeat(np.arange(rows), np.diff(rpt)), col] = val
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert rpt[-1] == np.count_nonzero(want)


def test_uniform_law_has_exactly_its_degree():
    rpt, col, _ = _operand({"nnz_per_row": 4}, "uniform", 5000, 3)
    assert (np.diff(rpt) == 4).all()
    c = col.reshape(-1, 4)
    assert (np.diff(c, axis=1) > 0).all()


def test_compare_counts_each_kind_of_fault():
    a = _operand({"nnz_per_row": 4}, "uniform", 200, 5)
    ref = reference.spgemm(a, a, 200)
    good = (ref[0], ref[1], ref[2].astype(np.float32))
    bad_val = (ref[0], ref[1], good[2] * np.float32(1.001))
    bad_col = (ref[0], ref[1].copy(), good[2])
    bad_col[1][0] += 1
    nan = (ref[0], ref[1], good[2].copy())
    nan[2][3] = np.nan
    refs = {(0, 0): ref}
    numbers, failed = check.compare(
        [((0, 0), good), ((0, 0), None), ((0, 0), bad_col)], refs, 1e-5)
    assert numbers["unanswered"] == 1 and numbers["structure_mismatch"] == 1
    assert numbers["value_rel_err"] < 1e-6 and failed == 2
    numbers, failed = check.compare([((0, 0), bad_val)], refs, 1e-5)
    assert numbers["value_rel_err"] > 1e-4 and failed == 1
    numbers, failed = check.compare([((0, 0), nan)], refs, 1e-5)
    assert numbers["value_rel_err"] == np.inf and failed == 1


# ------------------------------------------------------------------- traffic
TENANTS = {"loop": "open", "rate_per_s": 20.0, "pool_per_size": 3,
           "sizes": [{"shift": 6, "weight": 1}, {"shift": 7, "weight": 2},
                     {"shift": 8, "weight": 4}, {"shift": 9, "weight": 8}]}


def test_open_schedule_same_work_for_every_seed():
    a = loads.open_schedule(TENANTS, 30, 1)
    b = loads.open_schedule(TENANTS, 30, 2**31 + 12345)
    assert len(a) == len(b) == 600
    assert [(d, s) for d, s, _ in a] == [(d, s) for d, s, _ in b]
    assert [m for _, _, m in a] != [m for _, _, m in b]
    gaps = np.diff([0.0] + [d for d, _, _ in a])
    assert len(set(np.round(gaps, 9))) == 600    # exponential quantiles
    assert 28 < a[-1][0] < 31
    counts = np.bincount([s for _, s, _ in a])
    assert counts.tolist() == [40, 80, 160, 320]
    assert max(m for _, _, m in a) == 2
    assert sorted({(s, m) for _, s, m in a}) == sorted(
        {(s, m) for _, s, m in b})


@pytest.mark.parametrize("rows,member", [(32768, 0), (4096, 5), (300, 1)])
def test_power_law_holds_its_stated_mean(rows, member):
    """webbase's law keeps the published 3.1 entries a row exactly, each
    row's columns distinct and sorted, no row empty, hubs clipped."""
    a = _operand({"avg_nnz": 3.1, "alpha": 1.8}, "power_law", rows, member)
    deg = np.diff(a[0])
    assert a[0][-1] == round(3.1 * rows)
    assert deg.min() >= 1 and deg.max() <= min(rows, 155)
    r = np.repeat(np.arange(rows), deg)
    same_row = r[1:] == r[:-1]
    assert (np.diff(a[1].astype(np.int64))[same_row] > 0).all()


def test_closed_order_cycles_the_pool_in_a_seeded_order():
    batch = {"loop": "closed", "pool_per_size": 8,
             "sizes": [{"shift": 0, "weight": 1}]}
    it = loads.closed_order(batch, 5)
    got = [next(it) for _ in range(16)]
    assert sorted(got[:8]) == [(0, m) for m in range(8)]
    assert got[8:] == got[:8] and got[:8] != sorted(got[:8])


def test_seed_draws_values_not_structures():
    cfg = json.loads((Path(check.__file__).parent / "configs"
                      / "webbase.json").read_text())
    a = loads.make_operand(cfg, 4096, 1, 0, 3)
    b = loads.make_operand(cfg, 4096, 2**40 + 1, 0, 3)
    c = loads.make_operand(cfg, 4096, 1, 0, 4)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[2], b[2])
    assert not np.array_equal(a[0], c[0])


def test_control_fails_the_value_limit():
    """The reference in bfloat16 in the program's place reads far over
    every configuration's limit; the float64 reference itself reads 0."""
    for cfg_path in (Path(check.__file__).parent / "configs").glob("*.json"):
        cfg = json.loads(cfg_path.read_text())
        a = loads.make_operand(cfg, 2048, 99, 0, 0)
        ref = reference.spgemm(a, a, 2048)
        ctl = reference.spgemm(a, a, 2048, round_to=ml_dtypes.bfloat16)
        lim = check.limits(cfg)
        numbers, failed = check.compare([((0, 0), ctl)], {(0, 0): ref},
                                        lim["value_rel_err"])
        correct, _ = check.verdict(numbers, lim)
        assert not correct and failed == 1, cfg_path.name
        assert numbers["value_rel_err"] > 10 * lim["value_rel_err"]
