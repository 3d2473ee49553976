"""Typed SpGEMM error taxonomy (DESIGN.md §9).

Every failure mode of the plan/execute pipeline raises a subclass of
:class:`SpgemmError` carrying structured ``context`` (plan key, bucket /
panel / shard ids, observed vs planned capacities) so a caller — or the
serving engine the ROADMAP builds on top of this — can route, log and
degrade on failures without parsing message strings.

``SpgemmError`` subclasses :class:`ValueError` deliberately: every bare
``ValueError`` this taxonomy replaced keeps satisfying existing
``except ValueError`` callers, so typing the errors is purely additive.

Taxonomy::

    SpgemmError                  base; .context dict, JSON-serializable
    ├── OperandValidationError   malformed operand (CSR invariant broken)
    ├── PlanMismatchError        operand/mesh/template doesn't fit the plan
    ├── CapacityExhaustedError   output slots exhausted beyond recovery
    ├── ShardFailureError        an execution unit (shard/panel/bucket) died
    │   └── StragglerError       a unit blew its priced dispatch budget
    ├── AdmissionRejectedError   serving front end refused/shed the request
    └── DeadlineExceededError    request deadline passed before completion
"""
from __future__ import annotations


class SpgemmError(ValueError):
    """Base class: message plus a structured, JSON-serializable ``context``.

    ``context`` keys are free-form but the pipeline uses a stable
    vocabulary: ``plan_key`` (hash of the plan's static key), ``operand``,
    ``field``, ``row``, ``index``, ``bucket``/``buckets``, ``panel``,
    ``shard``/``shards``, ``unit``, ``observed``, ``planned``.
    """

    def __init__(self, message: str, **context):
        self.context = {k: v for k, v in context.items() if v is not None}
        super().__init__(message)

    def __str__(self) -> str:
        base = super().__str__()
        if not self.context:
            return base
        ctx = ", ".join(f"{k}={v!r}" for k, v in sorted(self.context.items()))
        return f"{base} [{ctx}]"


class OperandValidationError(SpgemmError):
    """An operand violates a CSR invariant (``core.validate.validate_csr``):
    non-monotone/mis-sized ``rpt``, out-of-range or unsorted ``col``,
    non-finite ``val``, or a broken dtype contract.  ``context`` pinpoints
    the field and the first offending row/entry."""


class PlanMismatchError(SpgemmError):
    """An operand, mesh or template does not match the plan it is used
    with: wrong shape/capacity at ``to_device``, a panel-plan operand whose
    structure fingerprint differs from the planned one, a mesh whose axis
    size differs from the planned shard count, a template misuse, or
    ``use_kernel=True`` on a backend that cannot compile the kernels."""


class CapacityExhaustedError(SpgemmError):
    """Output capacity was exhausted and could not (or was not allowed to)
    be recovered: the retry ladder ran out of rounds/ceiling with the
    exact-symbolic fallback disabled, or a truncated result reached
    ``reassemble``.  ``context`` names the offending buckets/panels with
    observed need vs planned capacity."""


class ShardFailureError(SpgemmError):
    """One execution unit failed: a shard/panel exhausted its ladder on the
    distributed path (surfaced by name instead of a collective hang), a
    gather buffer was starved below its payload, or a bucket executor
    raised mid-flight.  ``context`` names the unit (``shard``/``panel``/
    ``bucket``) and chains the original failure as ``__cause__``."""


class StragglerError(ShardFailureError):
    """An execution unit exceeded its :class:`~repro.core.plan.DispatchBudget`
    — the dispatch completed (or was abandoned) but took longer than the
    priced per-unit budget derived from :func:`repro.core.profiles.
    unit_seconds`.  A straggler is treated exactly like a dead unit: it
    subclasses :class:`ShardFailureError` so every containment path (per-unit
    recovery, circuit breakers, retry ladders) applies unchanged.
    ``context`` carries the unit info plus ``observed`` (elapsed seconds) and
    ``planned`` (the budget)."""


class AdmissionRejectedError(SpgemmError):
    """The serving front end (:mod:`repro.serve.spgemm_service`) refused a
    request instead of letting it hang or starve the fleet: the bounded
    queue was full (load shedding), the request's cost estimate exceeds the
    whole device budget (it can never be scheduled), or a circuit breaker
    is open for its template.  ``context`` carries ``request``, the
    admission decision (``reason``) and the observed vs planned quantity
    (queue depth vs capacity, estimated vs budget bytes)."""


class DeadlineExceededError(SpgemmError):
    """A request's deadline passed before it reached execution (expired
    while queued) or before its result was produced.  ``context`` carries
    ``request``, ``deadline`` and ``waited`` (seconds on the service
    clock)."""
