"""Mesh engine: span ``mesh-execute``, whole duration (its phase spans
included), summed over a query's extents, median — what ``mesh_execute_ms``
read while the span had no children."""
from layer_metrics.phase_spans import ENGINE, phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, (ENGINE,), inside=None)
