"""Front door: mean members of a ``QueryService.query_range_many`` call over
the window — growth of ``filodb_query_batch_members_total`` over growth of
``filodb_query_batches_total``. The front hands over in one call every
request that became readable in one pass of its loop, so with one client
this reads 1 and with six it says how many wait behind the one being
answered. Nothing where the program has no such counters."""
from measure import delta


def read(spans, counters, trace, run):
    w = counters["window"]
    n = delta(w, "filodb_query_batches_total")
    return delta(w, "filodb_query_batch_members_total") / n if n else None
