"""Parse and plan: spans ``parse`` + ``plan-materialize``, median a query."""
from measure import median, self_ms


def read(spans, counters, trace, run):
    return median(v for e in spans
                  if (v := self_ms(e, ("parse", "plan-materialize")))
                  is not None)
