"""Mesh engine: span ``batch-stack`` alone — allocating the padded
``[P, S]`` arrays and copying each series in; ``mesh_decode_ms`` less this
is the chunk reads."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("batch-stack",))
