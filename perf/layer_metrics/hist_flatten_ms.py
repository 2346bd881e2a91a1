"""Mesh engine: milliseconds of ``hist-flatten`` a request — the summed
duration of every ``hist-flatten`` span in the window's entries (the child
of ``mesh-pad`` that lays a histogram batch's buckets into the series axis,
``[P, S, B]`` to ``[P·B, S]``: the transposes, the timestamps and counts
repeated a bucket, the group ids) over the summed ``members`` of those
entries, as ``mesh_ms_per_request`` reckons requests. A mean over requests
and not a median over entries: only a batch-cache miss opens the span, and
in a dashboard whose panels share a placed batch that is one request of
three. Nothing where no recorded query has the span: a run that was not
traced, a window of scalar batches or of hits alone, or a program without
the span."""


def read(spans, counters, trace, run):
    total, members, seen = 0.0, 0, False
    for e in spans:
        if e.get("batched"):
            continue
        members += e.get("members") or 1
        for s in e.get("spans") or []:
            if s["name"] == "hist-flatten":
                seen = True
                total += s["duration_ms"]
    return total / members if seen and members else None
