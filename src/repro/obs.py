"""Spans and counters of the SpGEMM program, on the profiler's clock.

A span is on exactly while a JAX profiler session records
(``jax.profiler.TraceAnnotation.is_enabled()``): there is no flag of its
own.  Off, :func:`span` costs that one check and returns a shared no-op
context, and :func:`count` finds no open span; nothing blocks, copies or
synchronizes, so tracing never changes what the program does.

On, a span opens ``jax.profiler.TraceAnnotation("spgemm.<name>")``, so its
host interval lands in the profiler's trace on the same clock as the
device's ops (no annotation can start with the benchmark's ``bench:``), and
on closing appends a :class:`Record` to an in-memory ring: its id, name,
request id, parent span id, start and end on ``time.perf_counter`` and the
counters :func:`count` added to it while it was the innermost open span.
The request id and the parent come from a ``contextvars`` stack, so every
span opened under one :func:`request` shares its id.

The ring keeps the newest ``RING`` records; :func:`dropped` says how many
fell out, and a reader that needs them all gives up when it is not 0.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import time
from typing import NamedTuple

import jax

PREFIX = "spgemm."
RING = 65536

_enabled = jax.profiler.TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()
_request = contextvars.ContextVar("spgemm_request", default=None)
_open = contextvars.ContextVar("spgemm_open_spans", default=())
_ids = itertools.count(1)


class Record(NamedTuple):
    """One closed span."""
    id: int
    name: str
    request: int | None
    parent: int | None      # id of the span open around it, if any
    t0: float               # time.perf_counter() at open
    t1: float               # ... and at close
    counters: dict


class Ring:
    """The newest ``size`` records, and how many older ones fell out."""

    def __init__(self, size: int = RING) -> None:
        self._buf: collections.deque = collections.deque(maxlen=size)
        self.dropped = 0

    def append(self, rec: Record) -> None:
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1
        self._buf.append(rec)

    def records(self) -> list[Record]:
        return list(self._buf)


_RING = Ring()


def records() -> list[Record]:
    """Every record the ring holds, oldest first (a span is recorded when
    it closes, so a child comes before its parent)."""
    return _RING.records()


def dropped() -> int:
    """Records that fell out of the full ring."""
    return _RING.dropped


class _Span:
    __slots__ = ("name", "id", "parent", "request", "counters", "t0",
                 "_annotation", "_token")

    def __init__(self, name: str) -> None:
        self.name = name
        self.counters: dict = {}

    def __enter__(self) -> "_Span":
        stack = _open.get()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else None
        self.request = _request.get()
        self._token = _open.set(stack + (self,))
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        _open.reset(self._token)
        _RING.append(Record(self.id, self.name, self.request, self.parent,
                            self.t0, t1, self.counters))


def span(name: str):
    """A context manager timing ``name`` while the profiler records; a
    shared no-op otherwise."""
    return _Span(name) if _enabled() else _OFF


@contextlib.contextmanager
def _requesting(rid: int):
    token = _request.set(rid)
    try:
        yield
    finally:
        _request.reset(token)


def request(rid: int):
    """Spans opened inside carry request id ``rid`` (while the profiler
    records; a shared no-op otherwise)."""
    return _requesting(rid) if _enabled() else _OFF


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span (nothing
    when no span is open)."""
    stack = _open.get()
    if stack:
        counters = stack[-1].counters
        counters[name] = counters.get(name, 0) + n
