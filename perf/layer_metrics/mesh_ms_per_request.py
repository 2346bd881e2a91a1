"""Mesh engine: milliseconds of ``mesh-execute`` a request — the summed
duration of every ``mesh-execute`` span in the window's entries over the
summed ``members`` of those entries (1 where an entry has none: a request
the front door ran alone). An entry here may be a ``query-batch`` of
several requests, which is why this is a mean over requests and not, as the
phase readers beside it, a median over entries. A batch's members that were
recorded once more on their own (``batched``, no spans) are left out."""


def read(spans, counters, trace, run):
    total, members = 0.0, 0
    for e in spans:
        if e.get("batched"):
            continue
        members += e.get("members") or 1
        total += sum(s["duration_ms"] for s in e.get("spans") or []
                     if s["name"] == "mesh-execute")
    return total / members if members else None
