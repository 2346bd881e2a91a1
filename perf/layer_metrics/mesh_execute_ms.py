"""Mesh engine: span ``mesh-execute``, self time summed over a query's
extents, median over the queries that reached the engine."""
from measure import median, self_ms


def read(spans, counters, trace, run):
    return median(v for e in spans
                  if (v := self_ms(e, ("mesh-execute",))) is not None)
