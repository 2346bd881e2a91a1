"""Mesh engine and above: spans ``mesh-assemble`` (outputs to
``StepMatrix``), ``finish`` (materialize, limits, result budget) and
``cache-merge`` (slice and merge the result cache's extents)."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-assemble", "finish", "cache-merge"),
                        inside=None)
