"""The loader's own spans in a reduced capture, per unit of work.

The program opens ``repro.*`` spans (``jax.profiler.TraceAnnotation``)
at its layer boundaries: the cold start and its phases, the fetch tiers,
the fetch-to-decode hand-off, each decode tile, the kernel adapters'
host work around each launch, placement, and the stages of a publish.
``bench.trace.from_xspace`` keeps every host event that is not a
``bench.*`` span in ``Trace.host`` as (name, start, end), from every
thread of the host plane; the loader's spans are among them. A reader
here takes them inside each unit's span (``bench.coldstart`` or
``bench.publish``), clipped to it, and gives the mean over the units.

It returns None without a trace, and where no ``repro.*`` span lies in
the units at all: a program without the spans reads nothing, while one
whose spans are there but never waited reads 0."""
from __future__ import annotations

from bench.trace import union

PREFIX = "repro."


def per_unit(run, unit: str, seconds) -> float | None:
    """Mean over the ``unit`` spans of ``seconds(events)``, where
    ``events`` are the program spans inside one unit, clipped to it, as
    (name, start, end) in nanoseconds, and ``seconds`` returns
    nanoseconds."""
    trace = run.trace
    if trace is None:
        return None
    units = trace.spans_named(unit)
    program = [ev for ev in trace.host if ev[0].startswith(PREFIX)]
    total, seen = 0.0, False
    for lo, hi in units:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in program
                  if s < hi and e > lo]
        seen = seen or bool(inside)
        total += seconds(inside)
    return total / len(units) / 1e9 if seen else None


def summed(*names):
    """Total length of the spans named `names` (nested ones counted
    twice: the loader opens none of these inside another)."""
    names = set(names)
    return lambda events: sum(e - s for n, s, e in events if n in names)


def covered(prefix: str):
    """Time at least one span whose name starts with `prefix` was open,
    on any thread."""
    return lambda events: sum(
        e - s for s, e in union((s, e) for n, s, e in events
                                if n.startswith(prefix)))
