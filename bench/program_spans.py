"""The program's own spans and counters (``repro.obs``), for the readers
of ``program_span`` and ``program_counter`` metrics.

The program records only while a profiler session runs, so in the traced
run every record it holds is the window's.  A checkout whose program has
no ``repro.obs``, or whose ring dropped records, gives nothing to read.
"""


def records():
    """The window's records, or ``None`` where there is nothing to read."""
    try:
        from repro import obs
    except ImportError:
        return None
    if obs.dropped():
        return None
    return obs.records() or None


def seconds(recs, name: str, under: str | None = None) -> float:
    """Total seconds in spans called ``name`` (with ``under``: only those
    with an enclosing span called ``under``)."""
    by_id = {r.id: r for r in recs}

    def inside(r):
        p = by_id.get(r.parent)
        while p is not None and p.name != under:
            p = by_id.get(p.parent)
        return p is not None

    return sum(r.t1 - r.t0 for r in recs
               if r.name == name and (under is None or inside(r)))


def counted(recs, name: str) -> int:
    """Counter ``name`` summed over every span."""
    return sum(r.counters.get(name, 0) for r in recs)
