"""Mesh engine: placed batches found again, of all batches asked for."""
from measure import delta, share


def read(spans, counters, trace, run):
    w = counters["window"]
    return share(delta(w, "filodb_mesh_batch_cache_total", event="hit"),
                 delta(w, "filodb_mesh_batch_cache_total", event="miss"))
