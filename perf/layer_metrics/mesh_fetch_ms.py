"""Device programs: span ``mesh-fetch`` — the host blocked on the device
until the enqueued programs end, plus device-to-host of the outputs."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-fetch",))
