"""Result cache: extents answered from the cache, of all extents asked."""
from measure import delta, share


def read(spans, counters, trace, run):
    w = counters["window"]
    return share(delta(w, "filodb_result_cache_hits_total"),
                 delta(w, "filodb_result_cache_misses_total"))
