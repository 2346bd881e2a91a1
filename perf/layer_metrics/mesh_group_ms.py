"""Mesh engine: span ``mesh-group`` — range-vector keys, group keys and
group ids of the matched series."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-group",))
