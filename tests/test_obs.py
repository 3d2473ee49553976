"""The program's spans and counters (``repro.obs``): off without a profiler,
on the profiler's clock with one, and what the planner, executor and
reassembly count into them."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import plan as plan_mod
from repro.serve.spgemm_service import SpgemmService
from repro.sparse import random as sprand


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring for the test, so records of other tests do not leak in."""
    r = obs.Ring()
    monkeypatch.setattr(obs, "_RING", r)
    return r


@pytest.fixture
def traced(tmp_path):
    """Run the body under a profiler session writing into ``tmp_path``."""
    def run(fn):
        with jax.profiler.trace(str(tmp_path)):
            return fn()
    return run


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r.name, []).append(r)
    return out


def test_spans_record_nothing_without_a_profiler(ring):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.request(3), obs.span("outer"):
        obs.count("n", 5)
        with obs.span("inner"):
            obs.count("n")
    assert obs.span("x") is obs.span("y")      # the shared no-op
    assert obs.records() == [] and obs.dropped() == 0


def test_nested_spans_carry_parent_request_and_counters(ring, traced):
    def body():
        with obs.request(7):
            with obs.span("outer"):
                obs.count("bytes", 10)
                with obs.span("inner"):
                    obs.count("bytes", 2)
                    obs.count("bytes", 3)
                    obs.count("reruns")
        with obs.span("alone"):
            pass
    traced(body)
    recs = _by_name(obs.records())
    (outer,), (inner,), (alone,) = recs["outer"], recs["inner"], recs["alone"]
    # a span is recorded when it closes: the child before its parent
    assert [r.name for r in obs.records()] == ["inner", "outer", "alone"]
    assert inner.parent == outer.id and outer.parent is None
    assert inner.request == outer.request == 7 and alone.request is None
    assert inner.counters == {"bytes": 5, "reruns": 1}
    assert outer.counters == {"bytes": 10} and alone.counters == {}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 <= alone.t0


def test_full_ring_reports_what_it_dropped(monkeypatch, traced):
    monkeypatch.setattr(obs, "_RING", obs.Ring(2))

    def body():
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
    traced(body)
    assert [r.name for r in obs.records()] == ["s3", "s4"]
    assert obs.dropped() == 3


def test_span_names_land_on_the_profilers_host_plane(ring, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer"):
            with obs.span("inner"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"spgemm.outer", "spgemm.inner"} <= names
    assert not any(n.startswith("bench:") for n in names)


def _operand():
    return sprand.power_law(300, 300, 4, 1.6, seed=11)


def test_service_request_spans_share_its_id(ring, traced):
    a = _operand()
    untraced = SpgemmService()
    plain = untraced.submit(a, a)
    untraced.drain()

    svc = SpgemmService()
    req = traced(lambda: (svc.submit(a, a), svc.drain())[0])
    assert req.state == "DONE" and req.plan.retries == 0
    recs = obs.records()
    by = _by_name(recs)
    assert {r.request for r in recs} == {req.id}
    for top in ("submit.validate", "plan", "execute", "reassemble"):
        assert len(by[top]) == 1 and by[top][0].parent is None
    children = {
        "plan": {"plan.template", "plan.flop", "plan.upload",
                 "plan.predict", "plan.alloc"},
        "execute": {"execute.args", "execute.dispatch", "execute.wait"},
        "reassemble": {"reassemble.copy", "reassemble.to_csr"},
    }
    for top, names in children.items():
        tid = by[top][0].id
        assert {r.name for r in recs if r.parent == tid} >= names
    (wait,) = [r for r in by["wait"]
               if r.parent == by["plan.predict"][-1].id]
    assert wait.t1 <= by["plan"][0].t1

    # the padded output, copied once: col and val (8 B a slot), the true
    # row counts (4 B a row) and the overflow scalar
    rows, cap = req.plan.shape_a[0], req.plan.alloc.row_capacity
    total = {k: sum(r.counters.get(k, 0) for r in recs)
             for k in ("d2h_bytes", "out_slots", "reruns")}
    assert by["execute.wait"][0].counters == {"d2h_bytes": 4 * rows}
    assert by["reassemble.copy"][0].counters == {
        "d2h_bytes": 8 * rows * cap + 4}
    assert total == {"d2h_bytes": 8 * rows * cap + 4 * rows + 4,
                     "out_slots": rows * cap, "reruns": 0}
    # tracing changes nothing the program does
    for f in ("rpt", "col", "val"):
        np.testing.assert_array_equal(getattr(req.result, f),
                                      getattr(plain.result, f))


@pytest.mark.parametrize("n_panels", [0, 2])
def test_reruns_slots_and_copies_are_counted(ring, traced, n_panels):
    """``safety=0`` under-allocates by construction, so the retry ladder
    re-executes buckets: each is one ``execute.rerun`` span, and its output
    slots count beside the first pass's."""
    a = _operand()
    cache = plan_mod.PlanCache()
    p = plan_mod.plan_spgemm(a, a, safety=0.0, retry_safety=2.0,
                             n_panels=n_panels)
    pops = p.local_populations()
    if n_panels:
        first = int(sum(pop * p.panel_caps[i].sum()
                        for i, pop in enumerate(pops)))
    else:
        first = p.shape_a[0] * p.alloc.row_capacity

    def body():
        with obs.span("execute"):
            out = plan_mod.execute(p, a, a, cache=cache)
        with obs.span("reassemble"):
            plan_mod.reassemble(p, out)
        return out
    out = traced(body)
    by = _by_name(obs.records())
    assert p.retry_events
    reruns = by["execute.rerun"]
    assert len(reruns) == len(p.retry_events)
    assert all(r.counters["reruns"] == 1 for r in reruns)
    assert by["execute"][0].counters["out_slots"] == first
    assert [r.counters["out_slots"] for r in reruns] == [
        pops[e["bucket"]] * e["new_cap"] for e in p.retry_events]

    if n_panels:
        blocks = [(c, v) for i, bk in enumerate(p.binning.buckets)
                  if bk.n_rows for c, v in zip(out.cols[i], out.vals[i])]
        nnz_bytes = sum(n.nbytes for bn in out.row_nnz for n in bn)
    else:
        blocks = [(out.col, out.val)]
        nnz_bytes = out.row_nnz.nbytes
    assert by["execute.wait"][0].counters == {"d2h_bytes": nnz_bytes}
    assert by["reassemble.copy"][0].counters == {
        "d2h_bytes": sum(c.nbytes + v.nbytes for c, v in blocks) + 4}


def test_executors_keep_the_module_name_the_roofline_reads():
    """``executor_roofline`` sums the device time of modules named
    ``jit_run``: the local executor and the one-bucket retry executor must
    keep lowering to that name, with each bucket's pass in a named scope."""
    a = _operand()
    cache = plan_mod.PlanCache()
    p = plan_mod.plan_spgemm(a, a, pop_quant=True)
    local = plan_mod.lower_local(p, a, a, cache=cache)
    assert local.as_text().startswith("module @jit_run")
    text = local.as_text(debug_info=True)
    for i, bk in enumerate(p.binning.buckets):
        assert f"b{i}.{bk.route}" in text

    bk = p.binning.buckets[0]
    run = plan_mod._build_bucket_executor(
        plan_mod._bucket_meta(bk, 16), False, cache)
    ad, bd = p.to_device(a, "a"), p.to_device(a, "b")
    bucket = run.lower(ad, bd, jax.numpy.asarray(bk.rows))
    assert bucket.as_text().startswith("module @jit_run")
    assert f"unit.{bk.route}" in bucket.as_text(debug_info=True)
