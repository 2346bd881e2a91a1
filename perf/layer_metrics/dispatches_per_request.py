"""Result cache: mesh dispatches a request answered in the window cost —
growth of ``filodb_mesh_dispatch_total`` over the window, every form, over
the requests answered. Each dispatch is one blocking device round trip
while the result cache evaluates a request's missed extents one by one; a
batch that ran its members as one device program would read under 1."""
from measure import mesh_dispatches


def read(spans, counters, trace, run):
    before, after = counters["window"]
    if not any(s.startswith("filodb_mesh_dispatch_total") for s in after):
        return None
    answered = len(run.get("latencies_ms") or ())
    return mesh_dispatches((before, after)) / answered if answered else None
