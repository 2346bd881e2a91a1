"""Mesh engine: milliseconds of ``histogram_quantile`` over classic ``le``
bucket series a request — the summed duration of every ``hist-quantile``
span tagged ``form="le"`` in the window's entries (the step under
``mesh-assemble`` that groups the answered rows by their key without
``le``, orders them by bound, makes them monotonic and evaluates the
quantile) over the summed ``members`` of those entries, as
``hist_flatten_ms`` reckons requests: a mean over requests, since a panel
without a quantile opens no such span. Nothing where no recorded query has
the span so tagged: a run that was not traced, a window of first-class
histograms or of no quantile, or a program whose span has no ``form``."""


def read(spans, counters, trace, run):
    total, members, seen = 0.0, 0, False
    for e in spans:
        if e.get("batched"):
            continue
        members += e.get("members") or 1
        for s in e.get("spans") or []:
            if s["name"] == "hist-quantile" \
                    and (s.get("tags") or {}).get("form") == "le":
                seen = True
                total += s["duration_ms"]
    return total / members if seen and members else None
