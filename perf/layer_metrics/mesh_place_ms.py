"""Mesh engine: spans ``mesh-pad`` + ``mesh-place`` — the value lane, the
padding to the mesh and the host's side of the host-to-device put."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-pad", "mesh-place"))
