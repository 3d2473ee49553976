"""Whole runs on the CPU at small sizes: every cell end to end, the refusal
without a chip, and ``correct`` coming out false when the answers the
timed path produces are broken underneath.

A test of a cell that asks for more devices than this process has runs
again in a child process with that many virtual CPU devices (``XLA_FLAGS``
is read when JAX starts), so a cell of four chips is rehearsed as it is
measured: on a four-device ``data`` mesh."""
import json
import os
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
CHILD_S = 120       # time limit of a child process (about 25 s each)


def _chips(workload, bench=BENCH):
    return int(harness.cell_files(bench, workload)[0]["chips"])


def _elsewhere(request, devices: int) -> bool:
    """Whether this test needs more devices than this process has.  If so,
    it has just run, whole, in a child process with ``devices`` virtual CPU
    devices, and passed there; the caller returns."""
    import jax
    if jax.device_count() >= devices:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count={devices}").strip())
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         request.node.nodeid], cwd=request.config.rootpath, env=env,
        capture_output=True, text=True, timeout=CHILD_S)
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("1 passed"), proc.stdout[-2000:]
    return True


def _run_cell(workload, seed=5, traced=False, bench=BENCH):
    cell, config, traffic = harness.cell_files(bench, workload)
    return harness.run_cell(workload, cell, config, traffic, seed=seed,
                            seconds=1.0, traced=traced, t_start=0.0,
                            bench=bench, rows=ROWS, log=lambda _: None)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_every_cell_and_fails(workload, capsys, request):
    if _elsewhere(request, _chips(workload)):
        return
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
def test_traced_rehearsal_reports_host_metrics(workload, request):
    if _elsewhere(request, _chips(workload)):
        return
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
    c = harness.set_up(config, traffic, chips=1, seed=3, rows=256,
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


# ----------------------------------------------------- cells of many chips
def _with_cell(chips, config="webbase", traffic="batch"):
    """``BENCHMARK.json`` with one more cell, on ``chips`` devices."""
    name = f"{config}.{traffic}{chips}"
    cell = {"name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "a rehearsal of a cell of many chips"}
    return dict(BENCH, workloads=BENCH["workloads"] + [cell]), name


def _keep_set_up(monkeypatch) -> list:
    made = []
    set_up = harness.set_up

    def keep(*args, **kw):
        made.append(set_up(*args, **kw))
        return made[-1]
    monkeypatch.setattr(harness, "set_up", keep)
    return made


def _budget_per_device(monkeypatch, per=1 << 30) -> list:
    """The CPU reports no memory limit: give each device ``per`` bytes."""
    seen = []

    def budget(devices):
        seen.append(list(devices))
        return per * len(seen[-1])
    monkeypatch.setattr(harness, "device_budget", budget)
    return seen


def test_four_chip_cell_serves_on_a_data_mesh(request, monkeypatch):
    """A cell of four chips, added by its entry alone, runs the service on
    a four-device ``data`` mesh through the distributed executors, with
    the mesh's memory as its budget, and reads ``correct``."""
    if _elsewhere(request, 4):
        return
    import jax
    bench, name = _with_cell(4)
    made = _keep_set_up(monkeypatch)
    budgets = _budget_per_device(monkeypatch)
    result = _run_cell(name, bench=bench)
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    (c,) = made
    mesh = c.svc.config.mesh
    assert c.devices == jax.devices()[:4] and budgets == [c.devices]
    assert mesh.axis_names == ("data",) and list(mesh.devices) == c.devices
    assert c.svc.config.device_budget_bytes == 4 << 30
    plans = [r.plan for r in c.svc.requests]
    assert plans and all(p.mesh is mesh and p.num_shards == 4
                         for p in plans)


def test_one_chip_cell_on_four_devices_builds_no_mesh(request, monkeypatch):
    """On a host of four devices a one-chip cell takes the first, builds
    no mesh and plans against that one chip's memory."""
    if _elsewhere(request, 4):
        return
    import jax
    workload = next(w for w in CELLS if _chips(w) == 1)
    _, config, traffic = harness.cell_files(BENCH, workload)
    budgets = _budget_per_device(monkeypatch)
    c = harness.set_up(config, traffic, chips=1, seed=3, rows=256,
                       clock=harness.time.perf_counter, log=lambda _: None)
    assert c.devices == jax.devices()[:1] and budgets == [c.devices]
    assert c.svc.config.mesh is None
    assert c.svc.config.device_budget_bytes == 1 << 30
    assert all(not r.plan.distributed for r in c.svc.requests)


def test_too_few_devices_fail_and_print_nothing(capsys, monkeypatch):
    """A cell of more chips than JAX finds is refused, rehearsal or not:
    never a run on fewer devices."""
    import jax
    chips = max(4, jax.device_count() + 1)
    bench, name = _with_cell(chips)
    _, config, traffic = harness.cell_files(bench, name)
    with pytest.raises(harness.TooFewDevices, match=f"needs {chips} devices"):
        harness.set_up(config, traffic, chips=chips, seed=1, rows=256,
                       clock=harness.time.perf_counter, log=lambda _: None)
    monkeypatch.setattr(harness, "benchmark", lambda: bench)
    rc = run.main(["--workload", name, "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--rehearse", str(ROWS)])
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert f"needs {chips} devices" in err and "rehearsal result" not in err


class _Limit:
    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


def test_budget_is_the_sum_over_the_cells_chips():
    assert harness.device_budget([_Limit(16)]) == 16
    assert harness.device_budget([_Limit(16)] * 4) == 64
    assert harness.device_budget([_Limit(16), _Limit(None)]) is None


def test_executor_roofline_is_over_the_cells_chips():
    """Work that one chip's bandwidth moves in 1 s, over 2 s of executor
    time on each chip: 50 % of one chip, 12.5 % of four."""
    read = harness.metric_reader("executor_roofline")
    sent = [harness.Sent(0.0, (0, 0), 0.0, result=((), (), ()))]

    def share(chips, module):
        run = harness.Run(
            loop="closed", setup_s=1.0, window_s=5.0, sent=sent,
            peak_bytes=1, window_compiles=0, device_kind="TPU v5 lite",
            chips=chips, trace={"modules": {module: 2.0, "jit_other": 7.0}},
            work_bytes={(0, 0): 819e9})
        return read(run)
    assert share(1, "jit_run") == pytest.approx(50.0)
    assert share(4, "jit_shard_fn") == pytest.approx(12.5)
    assert share(4, "jit_run") == pytest.approx(12.5)
    assert share(1, "jit_other") is None


# ------------------------------------------------------------------ faults
def _keep_rows(c, keep_row):
    keep = np.repeat(keep_row, np.diff(c.rpt))
    c.rpt = np.concatenate([[0], np.cumsum(np.diff(c.rpt) * keep_row)])
    c.col, c.val = c.col[keep], c.val[keep]


def _alter_value(c, plan):
    c.val = c.val.copy()
    c.val[c.val.size // 2] *= np.float32(1.001)


def _alter_column(c, plan):
    c.col = c.col.copy()
    c.col[c.col.size // 2] = (c.col[c.col.size // 2] + 1) % c.shape[1]


def _drop_half_the_rows(c, plan):
    _keep_rows(c, np.arange(c.shape[0]) < c.shape[0] // 2)


def _lose_other_chips(c, plan):
    """The rows of every chip but the first never reach the answer: the
    gather of the shards left out."""
    first = np.zeros(c.shape[0], dtype=bool)
    for t in plan.shard_tables:
        first[t.table[0][t.valid[0]]] = True
    assert plan.num_shards > 1 and not first.all()
    _keep_rows(c, first)


FAULTS = [(w, f) for w in CELLS
          for f in [_alter_value, _alter_column, _drop_half_the_rows]
          + ([_lose_other_chips] if _chips(w) > 1 else [])]


def _run_broken(workload, fault, monkeypatch, bench=BENCH):
    from repro.core import plan as plan_mod
    reassemble = plan_mod.reassemble
    calls = []

    def broken(plan, *args, **kw):
        c = reassemble(plan, *args, **kw)
        calls.append(1)
        if len(calls) % 2:            # every other answer, from the start
            fault(c, plan)
        return c
    monkeypatch.setattr(plan_mod, "reassemble", broken)
    return _run_cell(workload, bench=bench)


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_broken_answers_are_not_correct(workload, fault, monkeypatch,
                                        request):
    if _elsewhere(request, _chips(workload)):
        return
    result = _run_broken(workload, fault, monkeypatch)
    assert not result["correct"]
    assert result["failed"] > 0


def test_lost_chips_are_not_correct(monkeypatch, request):
    """The fault a cell of many chips adds to those of one, on a cell of
    four: the rows of three chips never reach the answers."""
    if _elsewhere(request, 4):
        return
    bench, name = _with_cell(4)
    result = _run_broken(name, _lose_other_chips, monkeypatch, bench)
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
