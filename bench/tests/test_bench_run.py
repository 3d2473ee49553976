"""Whole runs on the CPU at small sizes: every cell end to end, the refusal
without a chip, and ``correct`` coming out false when the answers the
timed path produces are broken underneath."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import harness
import run

ROOT = Path(harness.ROOT)
ROWS = 1024
BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
OPEN = next(w["name"] for w in BENCH["workloads"]
            if harness.cell_files(BENCH, w["name"])[2]["loop"] == "open")


def _run_cell(workload, seed=5, traced=False):
    cell, config, traffic = harness.cell_files(BENCH, workload)
    return harness.run_cell(workload, cell, config, traffic, seed=seed,
                            seconds=1.0, traced=traced, t_start=0.0,
                            bench=BENCH, rows=ROWS, log=lambda _: None)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_every_cell_and_fails(workload, capsys):
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 7),
                   "--seconds", "1", "--trace", "0", "--rehearse", str(ROWS)])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    line = err.split("rehearsal result: ")[1].splitlines()[0]
    result = json.loads(line)
    assert result["correct"] and result["failed"] == 0
    assert list(result)[-1] == "check"
    want = {m["name"] for m in harness.cell_metrics(BENCH, workload, False)}
    # the CPU backend keeps no peak-memory counter: nothing to read
    assert set(result["metrics"]) == want - {"peak_hbm_gb"}


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal_reports_host_metrics(workload):
    result = _run_cell(workload, traced=True)
    assert result["correct"]
    names = set(result["metrics"])
    # the device-trace readers find no device plane on the CPU
    host = {m["name"] for m in harness.cell_metrics(BENCH, workload, True)
            if m["source"] != "device_trace"}
    assert names == host


def test_open_loop_reports_its_tail():
    result = _run_cell(OPEN)
    rate = harness.cell_files(BENCH, OPEN)[2]["rate_per_s"]
    assert result["correct"] and result["attempted"] == round(rate)
    assert set(result["metrics"]) == {"p95_s", "setup_s"}


class _Chip:
    """A device whose peak grows by one byte at each reading."""

    def __init__(self):
        self.peak = 0

    def memory_stats(self):
        self.peak += 1
        return {"peak_bytes_in_use": self.peak}


@pytest.mark.parametrize("window", ["closed_window", "open_window"])
def test_peak_is_read_at_a_fixed_answer(window):
    """``peak_hbm_gb`` is read once, at the traffic's
    ``peak_after_answers``-th answer of the window, however many answers
    the window then holds."""
    cell = CELLS[0] if window == "closed_window" else OPEN
    _, config, traffic = harness.cell_files(BENCH, cell)
    traffic = dict(traffic, peak_after_answers=3, rate_per_s=8.0)
    c = harness.set_up(config, traffic, seed=3, rows=256,
                       clock=harness.time.perf_counter, log=lambda _: None)
    chip = _Chip()
    probe = harness.PeakAt([chip], 3)
    seen = []

    def on_answer(n):
        seen.append(n)
        probe.answered(n)
        assert (probe.bytes is not None) == (n >= 3)
    sent, _, _ = getattr(harness, window)(
        c.svc, c.mats, traffic, 1.0, 3, harness.time.perf_counter,
        on_answer=on_answer)
    probe.close()
    assert len(sent) > 3 and seen == list(range(1, len(sent) + 1))
    assert probe.bytes == 1 and chip.peak == 1


def test_split_metrics_share_their_stems_reader():
    for name in ("plan_s.batch", "plan_s.tenants"):
        path = Path(harness.metric_reader(name).__code__.co_filename)
        assert path.name == "plan_s.py"
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric.batch")


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out, _ = capsys.readouterr()
    assert rc != 0 and out.strip() == ""


def test_benchmark_files_alone_refuse_to_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed", "1",
                              "--seconds", "1", "--trace", "0"]
    proc = subprocess.run([sys.executable] + cmd[1:], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# ------------------------------------------------------------------ faults
def _alter_value(c):
    c.val = c.val.copy()
    c.val[c.val.size // 2] *= np.float32(1.001)


def _alter_column(c):
    c.col = c.col.copy()
    c.col[c.col.size // 2] = (c.col[c.col.size // 2] + 1) % c.shape[1]


def _drop_half_the_rows(c):
    keep = int(c.rpt[c.shape[0] // 2])
    c.rpt = np.minimum(c.rpt, keep)
    c.col, c.val = c.col[:keep], c.val[:keep]


@pytest.mark.parametrize("fault", [_alter_value, _alter_column,
                                   _drop_half_the_rows])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_answers_are_not_correct(workload, fault, monkeypatch):
    from repro.core import plan as plan_mod
    reassemble = plan_mod.reassemble
    calls = []

    def broken(*args, **kw):
        c = reassemble(*args, **kw)
        calls.append(1)
        if len(calls) % 2:            # every other answer, from the start
            fault(c)
        return c
    monkeypatch.setattr(plan_mod, "reassemble", broken)
    result = _run_cell(workload)
    assert not result["correct"]
    assert result["failed"] > 0


def test_unanswered_requests_are_not_correct(monkeypatch):
    """Tenant requests the service never answers fail the check."""
    from repro.serve import spgemm_service
    execute_one = spgemm_service.SpgemmService._execute_one
    warm_up = harness.warm_up
    armed = []

    def warm_then_arm(*args):
        warm_up(*args)
        armed.append(1)

    def dropping(self, req, breaker):
        if armed and req.id % 3 == 0:
            self._finish(req, spgemm_service.RequestState.FAILED,
                         error=spgemm_service.ShardFailureError("dropped"))
            return
        execute_one(self, req, breaker)
    monkeypatch.setattr(harness, "warm_up", warm_then_arm)
    monkeypatch.setattr(spgemm_service.SpgemmService, "_execute_one",
                        dropping)
    result = _run_cell(OPEN)
    assert not result["correct"]
    assert result["check"]["unanswered"]["value"] > 0
