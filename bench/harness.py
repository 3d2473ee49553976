"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own and is found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment (law, rows, values, limits);
- ``laws/<law>.py``: the generator of that law's operands;
- ``traffic/<traffic>.json``: parameters for :mod:`loads`;
- ``metrics/<metric>.py``: a reader ``read(run) -> number | None``; a
  metric split by the end-to-end metric it moves (``plan_s.batch``,
  ``plan_s.tenants``) may share the reader of its stem (``plan_s.py``).

The system under test is ``SpgemmService`` with its own defaults, but for
the cell's chips: a cell of one chip runs it on the first device, with that
chip's memory as its device budget; a cell of more runs it on a 1-D
``data`` mesh of the first ``chips`` devices, with the mesh's memory as its
budget.  Each request is one product C = A·A, the paper's protocol.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import check
import loads
import reference
import roofline
import tracing

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

GRACE_S = 60.0      # how long past the window an answer may still come
MAX_STEPS = 16      # service steps one closed-loop product may take


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """``(cell, config, traffic)`` of a workload named in ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return (cell, load_json(ROOT / cfg["file"]),
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"))


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The end-to-end metrics a cell reports (untraced) or its per-layer
    metrics (traced)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in names
                                 else [])]


class CompileClock:
    """JAX's compile-phase seconds and the programs lowered (one
    ``jaxpr_to_mlir_module`` event each, whether or not the persistent
    cache then has the executable)."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.lowered = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == self.LOWERED:
            self.lowered += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Sent:
    """One request of the window, and what its ticket said once it was
    done (the ticket itself is not kept)."""
    due: float          # when it was due, on the run's clock
    key: tuple          # (size, member) of its operand in the pools
    submitted: float
    result: tuple | None = None     # host (rpt, col, val) of C
    state: str | None = None
    finished_at: float | None = None
    executing_at: float | None = None
    predicted_nnz: int | None = None

    def record(self, req) -> None:
        """Copy what the readers and the check need from a done ticket."""
        self.state = req.state
        self.finished_at = req.finished_at
        self.executing_at = next(
            (t for state, t in req.history if state == "EXECUTING"), None)
        if req.result is not None:
            r = req.result
            self.result = (r.rpt, r.col, r.val)
            self.predicted_nnz = int(req.plan.predicted_nnz)


class PeakAt:
    """``peak_bytes_in_use`` of the fullest chip, read once, when the
    window's ``n``-th answer is in (or at its close, if fewer came).  The
    service keeps every ticket it was given, and each ticket its plan's
    device copies of the operands, so the process's peak grows with the
    answers; read at a fixed count it is the same work in every run."""

    def __init__(self, devices, n: int) -> None:
        self.devices, self.n, self.bytes = devices, int(n), None

    def read(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def answered(self, count: int) -> None:
        if self.bytes is None and count >= self.n:
            self.bytes = self.read()

    def close(self) -> None:
        if self.bytes is None:
            self.bytes = self.read()


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    loop: str
    setup_s: float
    window_s: float
    sent: list
    peak_bytes: int     # PeakAt: at the traffic's ``peak_after_answers``
    window_compiles: int
    device_kind: str
    chips: int = 1      # the cell's devices, which share the work
    spans: object = None        # tracing.Spans, traced run only
    trace: dict | None = None   # tracing.summarize(), traced run only
    nnz_c: dict = dataclasses.field(default_factory=dict)
    work_bytes: dict = dataclasses.field(default_factory=dict)

    @property
    def answered(self) -> list:
        return [s for s in self.sent if s.result is not None]


def _csr(arrays, rows: int):
    from repro.sparse.formats import CSR
    rpt, col, val = arrays
    return CSR(rpt=rpt, col=col, val=val, shape=(rows, rows))


def serve_one(svc, a) -> object:
    """Submit one product and step the service until it is answered."""
    req = svc.submit(a, a)
    for _ in range(MAX_STEPS):
        if req.done:
            return req
        svc.step()
    raise RuntimeError(f"request {req.id} not answered in {MAX_STEPS} "
                       f"steps: {req.state}")


def warm_up(svc, mats, log) -> None:
    """Serve every pool operand once, then again each one served before
    the service's plan templates last grew (its plan was made against an
    older template, so its key may not be compiled yet), until a pass
    grows nothing."""
    order = [(s, m) for s in range(len(mats)) for m in range(len(mats[s]))]
    rounds = 0
    while order:
        rounds += 1
        last = -1
        for i, (s, m) in enumerate(order):
            g0 = svc.stats()["templates"]["growths"]
            req = serve_one(svc, mats[s][m])
            if req.result is None:
                raise RuntimeError(f"warm-up request {req.id} ended "
                                   f"{req.state}: {req.error}")
            if svc.stats()["templates"]["growths"] != g0:
                last = i
        order = order[:last + 1]
    log(f"warm-up: {rounds} passes")


def closed_window(svc, mats, traffic, seconds, seed, clock,
                  on_answer=lambda n: None) -> tuple:
    """One client: the next product goes when the last is answered.  The
    window closes at the first answer at or after ``seconds``."""
    sent = []
    t0 = clock()
    for key in loads.closed_order(traffic, seed):
        now = clock()
        sent.append(Sent(now, key, now))
        sent[-1].record(serve_one(svc, mats[key[0]][key[1]]))
        on_answer(len(sent))
        if clock() - t0 >= seconds:
            break
    return sent, t0, clock()


def open_window(svc, mats, traffic, seconds, seed, clock,
                on_answer=lambda n: None) -> tuple:
    """Requests due on the traffic's schedule over ``seconds``; the window
    closes when every one of them is answered (at most ``GRACE_S`` past
    the schedule's end)."""
    import jax
    schedule = loads.open_schedule(traffic, seconds, seed)
    sent, pending = [], []
    i = done = 0
    t0 = clock()
    while i < len(schedule) or pending:
        now = clock()
        while i < len(schedule) and t0 + schedule[i][0] <= now:
            due, s, m = schedule[i]
            sent.append(Sent(t0 + due, (s, m), clock()))
            pending.append((sent[-1], svc.submit(mats[s][m], mats[s][m])))
            i += 1
        still = []
        for rec, req in pending:
            if req.done:
                rec.record(req)
                done += 1
                on_answer(done)
            else:
                still.append((rec, req))
        pending = still
        if pending:
            svc.step()
            if clock() > t0 + seconds + GRACE_S:
                break
        elif i < len(schedule):
            with jax.profiler.TraceAnnotation(tracing.PREFIX + "wait"):
                time.sleep(max(0.0, t0 + schedule[i][0] - clock()))
    return sent, t0, clock()


@dataclasses.dataclass
class Cell:
    """A cell after set-up: the service, warmed up, and its operands."""
    svc: object
    pools: list         # pools[size][member] = (rpt, col, val)
    mats: list          # the same operands as the program's host CSR
    sizes: list         # rows of each size
    compiles: CompileClock
    devices: list


class TooFewDevices(RuntimeError):
    """The cell asks for more devices than JAX finds."""


def cell_devices(chips: int) -> list:
    """The first ``chips`` of JAX's devices.  Fewer is an error, never a
    smaller run (a rehearsal on the CPU gets more from ``XLA_FLAGS=
    --xla_force_host_platform_device_count=<chips>``)."""
    import jax
    devices = jax.devices()
    if len(devices) < chips:
        raise TooFewDevices(
            f"the cell needs {chips} devices; JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s)")
    return devices[:chips]


def device_budget(devices) -> int | None:
    """The sum of the devices' ``bytes_limit`` (one chip's for a one-chip
    cell), or ``None`` where a device reports none (the CPU).  The sum is
    what a mesh holds: ``admission.estimate_cost`` prices a plan made on a
    mesh as its whole product, every shard's output slots together
    (``planned_bytes``: ``shard_slots() * num_shards``)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return sum(int(x) for x in limits) if all(limits) else None


def set_up(config: dict, traffic: dict, *, chips: int, seed: int,
           rows: int | None, clock, log) -> Cell:
    """Operand pools from the seed, the service on the cell's devices, and
    its warm-up."""
    from jax.sharding import Mesh
    from repro.serve.spgemm_service import ServiceConfig, SpgemmService

    compiles = CompileClock()
    devices = cell_devices(chips)
    sizes = loads.size_rows(config, traffic, rows)
    pools = loads.make_pools(config, traffic, seed, rows)
    mats = [[_csr(op, r) for op in pool] for pool, r in zip(pools, sizes)]
    log(f"operands: rows {sizes}, {traffic['pool_per_size']} per size, "
        f"nnz {[int(np.mean([p[1].size for p in pool])) for pool in pools]}"
        f" (mean per size)")
    budget = device_budget(devices)
    # one chip: no mesh, the local executors; more: the distributed ones
    mesh = Mesh(np.array(devices), ("data",)) if chips > 1 else None
    # the sampler keeps the service's default seed: with the run's seed the
    # sampled prediction moves pow2 capacities, so each new seed compiled
    # new executors in set-up and changed the work (power law)
    svc = SpgemmService(ServiceConfig(
        device_budget_bytes=int(budget or ServiceConfig.device_budget_bytes),
        mesh=mesh), clock=clock)
    warm_up(svc, mats, log)
    log(f"set-up: compile {compiles.seconds:.3f}s, {compiles.lowered} "
        f"programs lowered, {compiles.cache_hits} persistent-cache hits")
    return Cell(svc, pools, mats, sizes, compiles, devices)


def _stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(workload: str, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, traced: bool, t_start: float,
             bench: dict, rows: int | None = None, log=_stderr) -> dict:
    """Set up, measure, check; return the result line's dict."""
    import jax

    clock = time.perf_counter
    chips = int(cell["chips"])
    c = set_up(config, traffic, chips=chips, seed=seed, rows=rows,
               clock=clock, log=log)
    svc, pools, mats, sizes = c.svc, c.pools, c.mats, c.sizes
    compiles, devices = c.compiles, c.devices
    dev = devices[0]

    spans = tracing.Spans(clock) if traced else None
    peak_at = PeakAt(devices, traffic["peak_after_answers"])
    trace_dir = tempfile.TemporaryDirectory() if traced else None
    lowered0 = compiles.lowered
    if traced:
        spans.install()
        # no Python tracer: it records every Python call, which made a
        # 30 s window's trace take minutes to write and read
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    setup_s = clock() - t_start
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            window = (closed_window if traffic["loop"] == "closed"
                      else open_window)
            sent, t0, t1 = window(svc, mats, traffic, seconds, seed, clock,
                                  on_answer=peak_at.answered)
    finally:
        if traced:
            jax.profiler.stop_trace()
            spans.uninstall()
    window_compiles = compiles.lowered - lowered0
    peak_at.close()
    peak = peak_at.read()
    if traffic["loop"] == "open":
        late = [s.submitted - s.due for s in sent]
        worst = int(np.argmax(late))
        log(f"generator: {len(sent)} requests, late by mean "
            f"{np.mean(late):.6f}s, max {late[worst]:.6f}s at "
            f"{sent[worst].due - t0:.3f}s into the window")
    states = collections.Counter(s.state for s in sent)
    log(f"window: {t1 - t0:.3f}s, {window_compiles} programs lowered, "
        f"requests by state {dict(states)}")
    run = Run(loop=traffic["loop"], setup_s=setup_s, window_s=t1 - t0,
              sent=sent, peak_bytes=peak_at.bytes,
              window_compiles=window_compiles,
              device_kind=dev.device_kind, chips=chips, spans=spans)
    if traced:
        path = next(Path(trace_dir.name).rglob("*.xplane.pb"))
        run.trace = tracing.summarize(tracing.load(str(path)))
        trace_dir.cleanup()

    # the check, on the host, once the window and the peak reading are done
    answers = [(s.key, s.result) for s in sent]
    refs = {}
    for key in sorted({s.key for s in sent}):
        a = pools[key[0]][key[1]]
        refs[key] = reference.spgemm(a, a, sizes[key[0]])
        run.nnz_c[key] = int(refs[key][0][-1])
        run.work_bytes[key] = roofline.spgemm_bytes(
            sizes[key[0]], a[1].size, reference.flop(a, a), run.nnz_c[key])
    lim = check.limits(config)
    numbers, failed = check.compare(answers, refs, lim["value_rel_err"])
    correct, table = check.verdict(numbers, lim)

    metrics = {}
    for m in cell_metrics(bench, workload, traced):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(sent),
              "failed": failed, "metrics": metrics,
              "device": device}
    if traced and run.trace is not None:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    for name, row in table.items():
        log(f"check {name} {row['value']} limit {row['limit']}")
    result["check"] = table
    return result
