"""Mesh engine: plans the mesh ran, of those and the ones it declined at
recognition or gave back to the exec tree."""
from measure import delta, mesh_dispatches, share


def read(spans, counters, trace, run):
    w = counters["window"]
    return share(mesh_dispatches(w),
                 delta(w, "filodb_mesh_unsupported_total")
                 + delta(w, "filodb_mesh_fallback_total"))
