"""Mesh engine: span ``decode`` under ``mesh-execute`` — ``build_batch``,
its children ``batch-read`` and ``batch-stack`` included."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("decode",))
