"""Readers of the program's own spans and counters (``repro.obs``), on
hand-made records."""
import sys

import pytest

import harness
from repro import obs

NEW = ("execute_wait_s.batch", "d2h_mb.batch", "plan_wait_s.tenants",
       "out_slots_per_nnz", "reruns.batch", "reruns.tenants")


def _rec(id, name, t0, t1, parent=None, request=0, **counters):
    return obs.Record(id, name, request, parent, t0, t1, counters)


# two requests: request 0 waited 0.25 s in planning and 1.5 s for its
# executor, was rerun once and copied 3 MB; request 1 waited 0.75 s in
# execute.wait and copied 1 MB.  A "wait" outside "plan" does not count.
RECORDS = [
    _rec(2, "wait", 0.0, 0.25, parent=1),
    _rec(1, "plan.predict", 0.0, 0.5, parent=0),
    _rec(0, "plan", 0.0, 1.0),
    _rec(4, "execute.wait", 1.0, 2.5, parent=3, d2h_bytes=1_000_000),
    _rec(5, "execute.rerun", 2.5, 2.75, parent=3, reruns=1, out_slots=50),
    _rec(3, "execute", 1.0, 3.0, out_slots=250),
    _rec(7, "reassemble.copy", 3.0, 3.5, parent=6, d2h_bytes=2_000_000),
    _rec(6, "reassemble", 3.0, 4.0),
    _rec(8, "execute.wait", 4.0, 4.75, request=1, d2h_bytes=1_000_000),
    _rec(9, "wait", 4.0, 9.0, request=1),
]


def _run():
    sent = [harness.Sent(0.0, (0, m), 0.0, result=((), (), ()))
            for m in range(2)]
    return harness.Run(loop="closed", setup_s=1.0, window_s=5.0, sent=sent,
                       peak_bytes=1, window_compiles=0, device_kind="cpu",
                       nnz_c={(0, 0): 100, (0, 1): 50})


@pytest.fixture
def ring(monkeypatch):
    r = obs.Ring()
    for rec in RECORDS:
        r.append(rec)
    monkeypatch.setattr(obs, "_RING", r)
    return r


def test_readers_on_hand_made_records(ring):
    got = {name: harness.metric_reader(name)(_run()) for name in NEW}
    assert got == pytest.approx({
        "execute_wait_s.batch": (1.5 + 0.75) / 2,
        "d2h_mb.batch": 4.0 / 2,
        "plan_wait_s.tenants": 0.25 / 2,
        "out_slots_per_nnz": 300 / 150,
        "reruns.batch": 0.5, "reruns.tenants": 0.5})


def test_readers_give_up_when_the_ring_dropped_records(ring):
    ring.dropped = 1
    assert all(harness.metric_reader(n)(_run()) is None for n in NEW)


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    """A checkout whose program has no ``repro.obs`` (the parent of the
    change that added it) reads nothing and raises nothing."""
    import repro
    ring = obs.Ring()
    ring.append(RECORDS[0])
    monkeypatch.setattr(obs, "_RING", ring)
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    with pytest.raises(ImportError):
        from repro import obs as _  # noqa: F401
    assert all(harness.metric_reader(n)(_run()) is None for n in NEW)


def test_readers_find_nothing_without_records(monkeypatch):
    monkeypatch.setattr(obs, "_RING", obs.Ring())
    assert all(harness.metric_reader(n)(_run()) is None for n in NEW)
