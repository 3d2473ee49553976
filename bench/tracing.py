"""Host spans around the program's entry points, and the reduction of a
profiler trace to device busy time, op and module times and idle gaps.

Spans are set from the benchmark's side only, in the traced run: the
module attributes the service calls (``plan_spgemm``, ``execute``,
``reassemble`` of ``repro.core.plan``; ``validate_pair`` of
``repro.core.validate``) are wrapped while the window runs.  The wrapper
around ``execute`` waits for its outputs, so device time is not charged to
``reassemble``.  Each span is kept on the host clock and is also written
into the profiler's trace as a ``bench:<name>`` annotation, so idle gaps
on the device can be laid against what the host was doing.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PREFIX = "bench:"
WINDOW = PREFIX + "window"

# (module, attribute, span name, wait for outputs)
ENTRY_POINTS = (
    ("repro.core.validate", "validate_pair", "validate", False),
    ("repro.core.plan", "plan_spgemm", "plan", False),
    ("repro.core.plan", "execute", "execute", True),
    ("repro.core.plan", "reassemble", "reassemble", False),
)


class Spans:
    """``records`` = ``[(name, t0, t1)]`` on ``clock``; a call nested in a
    call of the same name (``plan_spgemm`` plans a template's seed member
    through itself) is not recorded twice."""

    def __init__(self, clock) -> None:
        self.clock = clock
        self.records: list[tuple[str, float, float]] = []
        self._saved: list = []
        self._depth: dict = defaultdict(int)

    def _wrap(self, name: str, fn, wait: bool):
        import jax

        def inner(*args, **kw):
            if self._depth[name]:
                return fn(*args, **kw)
            self._depth[name] += 1
            try:
                with jax.profiler.TraceAnnotation(PREFIX + name):
                    t0 = self.clock()
                    out = fn(*args, **kw)
                    if wait:
                        jax.block_until_ready(out)
                    self.records.append((name, t0, self.clock()))
            finally:
                self._depth[name] -= 1
            return out
        return inner

    def install(self) -> None:
        import importlib
        for mod_name, attr, name, wait in ENTRY_POINTS:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)
            self._saved.append((mod, attr, fn))
            setattr(mod, attr, self._wrap(name, fn, wait))

    def uninstall(self) -> None:
        while self._saved:
            mod, attr, fn = self._saved.pop()
            setattr(mod, attr, fn)

    def total(self, name: str) -> float:
        return sum(t1 - t0 for n, t0, t1 in self.records if n == name)


def _short(name: str) -> str:
    """``%sort.6 = (f32[...]) sort(...)`` → ``sort.6``: an op's name without
    its HLO text."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    """The parts of an ``.xplane.pb`` trace the reduction reads:
    ``{"host": [[name, start_ns, end_ns]], "devices": {plane: {"ops": [[name,
    start_ns, end_ns]], "modules": [...]}}}`` (host: ``bench:`` annotations
    only)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend([_short(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events
                            if e.name.startswith(PREFIX))
    return {"host": host, "devices": devices}


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _module_name(name: str) -> str:
    """``jit_run(12)`` → ``jit_run``: one name per program, not per launch."""
    return re.sub(r"\(\d+\)$", "", name)


def summarize(events: dict, top: int = 10) -> dict | None:
    """Device busy and idle time inside the ``bench:window`` span.

    Returns ``None`` when the trace holds no device plane or no window:
    nothing to read.  Times are in seconds; per-device figures are averaged
    over the device planes.  ``idle_gaps`` gives, for each host activity,
    the device idle time that fell inside it: a gap that spans several
    ``bench:`` spans is split among them, each stretch going to the
    innermost span open there (``host`` where none was open)."""
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    devices = events["devices"]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    spans = [(s, e, n[len(PREFIX):]) for n, s, e in events["host"]
             if n != WINDOW]
    # stretches between span edges, each labelled by its innermost span
    # (longest first, so a span nested inside another overwrites it)
    cut = np.unique(np.clip([lo, hi] + [x for s, e, _ in spans
                                        for x in (s, e)], lo, hi))
    mids = (cut[:-1] + cut[1:]) / 2
    label = np.zeros(mids.size, dtype=np.int64)   # 0: no span open
    names = ["host"]
    for s, e, name in sorted(spans, key=lambda x: x[0] - x[1]):
        i0, i1 = np.searchsorted(mids, [s, e], side="left")
        names.append(name)
        label[i0:i1] = len(names) - 1
    busy = 0.0
    ops: dict = defaultdict(float)
    modules: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    for dev in devices.values():
        merged = _union(((s, e) for _, s, e in dev["ops"]), lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, e in dev["ops"]:
            ops[name] += max(0.0, min(e, hi) - max(s, lo))
        for name, s, e in dev["modules"]:
            modules[_module_name(name)] += max(0.0, min(e, hi) - max(s, lo))
        # busy time before t, as a piecewise-linear function of t
        done = np.cumsum([0.0] + [e - s for s, e in merged])
        x = np.array([t for se in merged for t in se] or [lo], dtype=float)
        y = np.column_stack([done[:-1], done[1:]]).ravel() if merged else [0.0]
        idle = np.diff(cut) - np.diff(np.interp(cut, x, y))
        for i, length in zip(label, idle):
            if length > 0:
                gaps[names[i]] += float(length)
    n = len(devices)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "devices": n, "device_ops": ranked(ops),
            "modules": {k: v / n / 1e9 for k, v in modules.items()},
            "idle_gaps": ranked(gaps)}
