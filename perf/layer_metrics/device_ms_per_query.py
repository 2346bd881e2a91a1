"""Device programs: seconds in which an operation ran on the device in the
traced slice, over the mesh dispatches the program counted in that slice
(one a query where nothing is cached; one a missed extent otherwise)."""
from measure import mesh_dispatches


def read(spans, counters, trace, run):
    if not trace or not counters.get("slice"):
        return None
    n = mesh_dispatches(counters["slice"])
    return 1000.0 * trace["busy_s"] / n if n else None
