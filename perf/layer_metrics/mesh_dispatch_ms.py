"""Device programs: span ``mesh-dispatch`` — program lookup, the prepared
arrays and enqueueing every chunk's program (asynchronous: the device's
time shows in ``mesh_fetch_ms``)."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-dispatch",))
