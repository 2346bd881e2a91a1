"""Front door: mean seconds a request spent rendering its result into Prom
JSON over the window — growth of ``filodb_http_render_seconds_sum`` over
growth of ``_count``. The program takes the duration itself, after the
query's trace has closed, which is why it is a counter and not a span."""
from measure import delta


def read(spans, counters, trace, run):
    w = counters["window"]
    n = delta(w, "filodb_http_render_seconds_count")
    return 1000.0 * delta(w, "filodb_http_render_seconds_sum") / n \
        if n else None
